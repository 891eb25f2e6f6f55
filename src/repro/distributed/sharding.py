"""Sharding policy: parameter/batch/cache PartitionSpecs per (config, mesh).

Policy (GSPMD does the propagation; we pin the state):
  * tensor-parallel ("model" axis): attention heads, FFN hidden, MoE experts
    (expert-parallel when n_experts divides the axis, else TP inside each
    expert), Mamba d_inner / SSM heads, and the vocab dim of embed/lm_head;
  * FSDP ("data" axis): the non-TP dim of every large 2D+ weight, enabled
    when the per-device replicated footprint would exceed ``fsdp_threshold``
    bytes (big archs: grok-1, yi-34b, llama3-405b);
  * every sharding falls back to replication when the dim is not divisible
    by the mesh axis (e.g. qwen2-vl's 12 heads on a 16-way model axis);
  * the "pod" axis is never used for parameters — pods replicate the model
    and are CADA's communication-adaptive workers.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.flat import spec_dim
from repro.models.config import ModelConfig, param_count
from repro.models.model import abstract_params

FSDP_THRESHOLD = 6e9  # bytes of bf16 params per model-shard before FSDP


# ------------------------------------------------------- flat state plane

@dataclass(frozen=True)
class FlatSharding:
    """Static description of how the flat state plane shards over a mesh.

    ``axes`` are the mesh axes the (n_flat,) SERVER planes (θ̂/h/v̂/∇) shard
    over (ZeRO-style, from ``TrainHParams.state_fsdp_axes`` /
    ``shard_cada_state`` / the FSDP axes); ``waxis`` is the worker axis
    leading the (M, n_flat) planes. Hashable, so the kernel wrappers in
    kernels/ops.py can take it as a static argument and build the
    shard_map'd, psum-reduced forms around the Pallas/jnp kernels.
    """
    mesh: Any
    waxis: str
    axes: tuple

    @property
    def col_axes(self) -> tuple:
        """State-shard axes of the FLAT dim of worker planes: the server
        axes minus the worker axis (one spec may not repeat an axis)."""
        return tuple(a for a in self.axes if a != self.waxis)

    @property
    def shards(self) -> int:
        """State-shard count = required divisor of ``FlatLayout.n_flat``."""
        s = 1
        for a in self.axes:
            s *= int(self.mesh.shape[a])
        return s

    def server_spec(self) -> P:
        """(n_flat,) server-plane PartitionSpec."""
        return P(spec_dim(self.axes))

    def worker_spec(self) -> P:
        """(M, n_flat) worker-plane PartitionSpec."""
        return P(self.waxis, spec_dim(self.col_axes))

    def constrain_server(self, x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.server_spec()))

    def constrain_worker(self, x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.worker_spec()))


def _axsize(mesh, name):
    return mesh.shape[name] if name in mesh.shape else 1


def wants_fsdp(cfg: ModelConfig, mesh) -> bool:
    per_shard = 2 * param_count(cfg) / _axsize(mesh, "model")
    return per_shard > FSDP_THRESHOLD


def param_pspecs(cfg: ModelConfig, mesh, fsdp: bool | None = None,
                 fsdp_axes: tuple = ("data",)) -> Any:
    """Pytree of PartitionSpec matching init_params(cfg).

    ``fsdp_axes`` — mesh axes the FSDP dim shards over. The default shards
    over "data" only (params replicate across pods: CADA's workers); passing
    ("data", "pod") extends FSDP/ZeRO across pods for the 314B/405B archs
    whose optimizer state cannot replicate per pod.
    """
    if fsdp is None:
        fsdp = wants_fsdp(cfg, mesh)
    msize = _axsize(mesh, "model")

    def m_if(n):  # "model" when divisible, else replicate
        return "model" if (msize > 1 and n % msize == 0) else None

    def f_if(n):  # fsdp axes (largest divisible prefix), else replicate
        if not fsdp:
            return None
        kept, prod = [], 1
        for a in fsdp_axes:
            sz = _axsize(mesh, a)
            if sz > 1 and n % (prod * sz) == 0:
                kept.append(a)
                prod *= sz
        if not kept:
            return None
        return kept[0] if len(kept) == 1 else tuple(kept)

    d, ff, hd = cfg.d_model, cfg.d_ff, cfg.hd
    di, e = cfg.d_inner, cfg.n_experts
    heads_shardable = cfg.n_heads and (cfg.n_heads * hd) % msize == 0 \
        and cfg.n_heads % msize == 0
    kv_shardable = cfg.n_kv_heads and cfg.n_kv_heads % msize == 0
    expert_parallel = e > 0 and e % msize == 0

    def rule(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", None)) for k in path]
        name = names[-1]
        stacked = "blocks" in names           # leading n_layers axis
        expert = (len(leaf.shape) - (1 if stacked else 0)) == 3  # (E, a, b)

        def wrap(spec):
            if stacked:
                return P(*((None,) + tuple(spec)))
            return P(*spec)

        if name == "embed":
            return P(m_if(cfg.vocab), f_if(d))
        if name == "lm_head":
            return P(f_if(d), m_if(cfg.vocab))
        if name in ("final_norm", "ln", "ln1", "ln2"):
            return wrap((None,))
        if name == "wq":
            return wrap((f_if(d), "model" if heads_shardable else None))
        if name in ("wk", "wv"):
            return wrap((f_if(d), "model" if kv_shardable else None))
        if name == "wo":
            return wrap(("model" if heads_shardable else None, f_if(d)))
        if name == "router":
            return wrap((f_if(d), None))
        if name in ("w_gate", "w_up"):
            if expert:
                if expert_parallel:
                    # expert-parallel + FSDP on the d dim (314B experts
                    # cannot replicate within an expert shard)
                    return wrap(("model", f_if(d), None))
                return wrap((None, f_if(d), m_if(ff)))
            return wrap((f_if(d), m_if(ff)))
        if name == "w_down":
            if expert:
                if expert_parallel:
                    return wrap(("model", None, f_if(d)))
                return wrap((None, m_if(ff), f_if(d)))
            return wrap((m_if(ff), f_if(d)))
        # ----- mamba -----
        if name in ("in_x", "in_z"):
            return wrap((f_if(d), m_if(di)))
        if name in ("in_b", "in_c", "in_dt"):
            return wrap((f_if(d), None))
        if name == "conv_w":
            return wrap((None, m_if(di)))
        if name in ("conv_b", "out_norm"):
            return wrap((m_if(di),))
        if name in ("xp_dt", "xp_b", "xp_c"):
            return wrap((m_if(di), None))
        if name == "dt_proj":
            return wrap((None, m_if(di)))
        if name == "dt_bias":
            n0 = leaf.shape[1 if stacked else 0]
            return wrap((m_if(n0),))
        if name in ("A_log", "D"):
            dims = leaf.shape[(1 if stacked else 0):]
            spec = [m_if(dims[0])] + [None] * (len(dims) - 1)
            return wrap(tuple(spec))
        if name == "out_proj":
            return wrap((m_if(di), f_if(d)))
        # default: replicate
        return P(*(None,) * leaf.ndim)

    aps = abstract_params(cfg)
    return jax.tree_util.tree_map_with_path(rule, aps)


def _data_axes(mesh):
    """All batch-shardable axes, biggest meshes first: ('pod','data')."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def _axes_if(mesh, axes, n):
    """Largest prefix of ``axes`` whose product divides n (else None)."""
    kept = []
    prod = 1
    for a in axes:
        if n % (prod * _axsize(mesh, a)) == 0:
            kept.append(a)
            prod *= _axsize(mesh, a)
    if not kept:
        return None
    return kept[0] if len(kept) == 1 else tuple(kept)


def batch_pspecs(batch_specs: Any, mesh) -> Any:
    """Shard the leading (batch) dim of every batch leaf over the data axes
    (('pod','data') on the multi-pod mesh), guarded by divisibility; M-RoPE
    "positions" (3, B, S) shards its second dim."""
    axes = _data_axes(mesh)

    def rule(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", None)) for k in path]
        if "positions" in names:
            return P(None, _axes_if(mesh, axes, leaf.shape[1]),
                     *(None,) * (leaf.ndim - 2))
        return P(_axes_if(mesh, axes, leaf.shape[0]),
                 *(None,) * (leaf.ndim - 1))

    return jax.tree_util.tree_map_with_path(rule, batch_specs)


def cache_pspecs(cfg: ModelConfig, cache_specs: Any, mesh) -> Any:
    """Decode-cache sharding.

    k/v: (L, B, W, Hkv, hd); conv: (L, B, K-1, di); ssm: (L, B, ...).
    Batch shards over the data axes (divisibility-guarded); KV heads /
    d_inner / SSM heads over "model". When KV heads don't divide the model
    axis (GQA kv=8 on a 16-way axis) the ring dim W picks up the model axis
    instead so the 32k-context caches still fit per chip.
    """
    msize = _axsize(mesh, "model")
    daxes = _data_axes(mesh)

    def m_if(n):
        return "model" if (msize > 1 and n % msize == 0) else None

    def rule(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", None)) for k in path]
        name = names[-1]
        if name in ("index", "slot_pos"):
            return P(*(None,) * leaf.ndim)
        if name in ("k", "v"):
            b_ax = _axes_if(mesh, daxes, leaf.shape[1])
            h_ax = m_if(leaf.shape[3])
            w_ax = None
            if h_ax is None:
                w_ax = m_if(leaf.shape[2])
            return P(None, b_ax, w_ax, h_ax, None)
        if name == "conv":
            return P(None, _axes_if(mesh, daxes, leaf.shape[1]), None,
                     m_if(leaf.shape[3]))
        if name == "ssm":
            spec = [None, _axes_if(mesh, daxes, leaf.shape[1]),
                    m_if(leaf.shape[2])]
            spec += [None] * (leaf.ndim - 3)
            return P(*spec)
        return P(*(None,) * leaf.ndim)

    return jax.tree_util.tree_map_with_path(rule, cache_specs)


def to_named(mesh, pspecs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))
