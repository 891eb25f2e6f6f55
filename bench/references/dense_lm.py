"""Plain reference of a dense decoder LM: RMSNorm, multi-head or grouped
attention with rotary embeddings on the first ``rotary_pct`` of each head,
a SwiGLU feed-forward, an untied output head, mean next-token NLL.

Straight ``jax.numpy`` in float32, one layer after another, no kernels, no
remat, no scan, every matrix product at ``precision`` (``"highest"`` for
the reference). Nothing here imports the program.

``precision="fp8"`` is the control, the step below bfloat16 that would
tempt a change: every matrix product takes its operands rounded to float8
e4m3 and, in the backward pass, its cotangent rounded to float8 e5m2, each
with a per-tensor scale (the usual float8 training recipe); the rest stays
float32.

The equations are the program's configuration's, which departs from the
published stablelm-2-1.6b: that model has LayerNorm with a bias where this
has RMSNorm with none, and biases on the query, key and value projections
where this has none (its configuration file lists both under
``departures``, and ``BENCHMARK.json`` under ``reduced``). InternLM2 has
RMSNorm and no biases, as here. The rotary frequencies are
``theta ** (-i / (rot / 2))`` over the rotated half-pairs (the "rotate
half" layout), as both published implementations use.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

INIT_STD = 0.02
FP8_FWD = (jnp.float8_e4m3fn, 448.0)
FP8_BWD = (jnp.float8_e5m2, 57344.0)
HIGHEST = jax.lax.Precision.HIGHEST


def head_dim(cfg: dict) -> int:
    return int(cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"])


def init_weights(cfg: dict, key) -> dict:
    """The weights a seed gives, in the stored dtype (bfloat16): normal of
    standard deviation 0.02 for every matrix, ones for the norms, drawn
    from ``key`` split as the trainer splits it (embedding, blocks, head;
    per block attention, feed-forward), so that one seed means one model."""
    d, hq, hkv, hd = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                      head_dim(cfg))
    dt = jnp.dtype(cfg["dtype"])

    def normal(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * INIT_STD
                ).astype(dt)

    def block(k):
        k_attn, k_mlp, _ = jax.random.split(k, 3)
        kq, kk, kv, ko = jax.random.split(k_attn, 4)
        ku, kd, kg = jax.random.split(k_mlp, 3)
        return {
            "ln1": jnp.ones((d,), dt),
            "ln2": jnp.ones((d,), dt),
            "attn": {"wq": normal(kq, (d, hq * hd)),
                     "wk": normal(kk, (d, hkv * hd)),
                     "wv": normal(kv, (d, hkv * hd)),
                     "wo": normal(ko, (hq * hd, d))},
            "mlp": {"w_up": normal(ku, (d, cfg["d_ff"])),
                    "w_down": normal(kd, (cfg["d_ff"], d)),
                    "w_gate": normal(kg, (d, cfg["d_ff"]))},
        }

    k_emb, k_blocks, k_head, _ = jax.random.split(key, 4)
    blocks = jax.vmap(block)(jax.random.split(k_blocks, cfg["n_layers"]))
    return {"embed": normal(k_emb, (cfg["vocab"], d)),
            "blocks": blocks,
            "final_norm": jnp.ones((d,), dt),
            "lm_head": normal(k_head, (d, cfg["vocab"]))}


def _round8(x, fmt):
    dtype, top = fmt
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_product(fn, a, b):
    return fn(_round8(a, FP8_FWD), _round8(b, FP8_FWD))


def _fp8_fwd(fn, a, b):
    qa, qb = _round8(a, FP8_FWD), _round8(b, FP8_FWD)
    return fn(qa, qb), (qa, qb)


def _fp8_bwd(fn, res, g):
    _, vjp = jax.vjp(fn, *res)
    return vjp(_round8(g, FP8_BWD))


_fp8_product.defvjp(_fp8_fwd, _fp8_bwd)


def _product(precision: str):
    """``product(fn, a, b)`` of a named precision; ``fn`` is the exact
    float32 product at the highest matmul precision."""
    if precision == "fp8":
        return _fp8_product
    if precision != "highest":
        raise ValueError(f"no precision {precision!r}")
    return lambda fn, a, b: fn(a, b)


def _matmul(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


_EINSUMS = {spec: partial(jnp.einsum, spec, precision=HIGHEST)
            for spec in ("bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd")}


def loss(params, tokens, cfg: dict, precision: str = "highest"):
    """Mean next-token NLL of ``tokens`` (B, S + 1) under ``params``."""
    product = _product(precision)

    def mm(a, b):
        return product(_matmul, a, b)

    def ein(spec, a, b):
        return product(_EINSUMS[spec], a, b)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + cfg["norm_eps"]) * w

    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    b, s = inputs.shape
    hq, hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], head_dim(cfg)
    rot = int(hd * cfg["rotary_pct"])
    half = rot // 2
    freqs = 1.0 / (cfg["rope_theta"] ** (jnp.arange(half, dtype=jnp.float32)
                                         / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(x):
        x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                                rest], -1)

    causal = jnp.tril(jnp.ones((s, s), bool))
    x = p["embed"][inputs]
    for layer in range(cfg["n_layers"]):
        lp = jax.tree.map(lambda a: a[layer], p["blocks"])
        h = rms(x, lp["ln1"])
        q = rope(mm(h, lp["attn"]["wq"]).reshape(b, s, hq, hd))
        k = rope(mm(h, lp["attn"]["wk"]).reshape(b, s, hkv, hd))
        v = mm(h, lp["attn"]["wv"]).reshape(b, s, hkv, hd)
        k = jnp.repeat(k, hq // hkv, axis=2)    # query head j reads kv j//g
        v = jnp.repeat(v, hq // hkv, axis=2)
        sc = ein("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = ein("bhqk,bkhd->bqhd", pr, v).reshape(b, s, hq * hd)
        x = x + mm(o, lp["attn"]["wo"])
        h = rms(x, lp["ln2"])
        f = jax.nn.silu(mm(h, lp["mlp"]["w_gate"])) * mm(h, lp["mlp"]["w_up"])
        x = x + mm(f, lp["mlp"]["w_down"])
    logits = mm(rms(x, p["final_norm"]), p["lm_head"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
