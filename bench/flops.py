"""Operations and bytes, computed from shapes: the yardstick of ``mfu`` and
of the kernels' roofline shares. Nothing here reads the program."""
from __future__ import annotations

FLAT_ALIGN = 8   # the flat plane pads its length to 8 · state shards
F32 = 4


def head_dim(cfg: dict) -> int:
    return int(cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"])


def matmul_params_per_layer(cfg: dict) -> int:
    """Weights of one dense block that enter a matrix multiplication."""
    d, hd = cfg["d_model"], head_dim(cfg)
    attn = d * cfg["n_heads"] * hd * 2 + d * cfg["n_kv_heads"] * hd * 2
    mult = 3 if cfg["mlp_act"] in ("swiglu", "geglu") else 2
    return attn + mult * d * cfg["d_ff"]


def param_count(cfg: dict) -> int:
    """Every parameter: embedding, blocks with their two norms, the final
    norm and the output head."""
    d, v = cfg["d_model"], cfg["vocab"]
    head = 0 if cfg.get("tie_embeddings") else d * v
    return (v * d + head + d
            + cfg["n_layers"] * (matmul_params_per_layer(cfg) + 2 * d))


def n_flat(cfg: dict, shards: int = 1) -> int:
    """Length of the trainer's flat state plane."""
    n = param_count(cfg)
    step = FLAT_ALIGN * shards
    return n + (-n) % step


def model_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward operations one training token requires:
    6 per matmul weight (the embedding lookup is none) plus the attention
    scores and values, 12 · L · (heads · head_dim) · S, counted over the
    full S×S square as PaLM's appendix B counts them. Recomputation under
    remat and CADA's second gradient evaluation are not model work."""
    d = cfg["d_model"]
    n_mm = cfg["n_layers"] * matmul_params_per_layer(cfg)
    if not cfg.get("tie_embeddings"):
        n_mm += d * cfg["vocab"]
    attn = 12 * cfg["n_layers"] * cfg["n_heads"] * head_dim(cfg) * seq
    return 6.0 * n_mm + attn


def amsgrad_bytes_per_chip(cfg: dict, state_shards: int) -> int:
    """Least HBM traffic of one fused AMSGrad call on one chip: θ, h, v̂
    and ∇̄ read and θ', h', v̂' written, each an fp32 slice of the plane."""
    return 7 * F32 * n_flat(cfg, state_shards) // state_shards


def lhs_bytes_per_chip(cfg: dict, workers: int, chips: int,
                       state_shards: int) -> int:
    """Least HBM traffic of one batched rule-LHS call on one chip: the
    fresh and the second fp32 gradient planes of the worker rows it
    holds, each read once."""
    rows = workers // chips
    return 2 * F32 * rows * n_flat(cfg, state_shards)
