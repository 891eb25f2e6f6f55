"""Production meshes (TPU v5e targets).

single pod:  (16, 16)    axes ("data", "model")        — 256 chips
multi-pod:   (2, 16, 16) axes ("pod", "data", "model") — 512 chips

Functions, not module constants: importing this module never touches jax
device state (the dry-run launcher must set XLA_FLAGS before first init).
Every mesh is all-Auto: GSPMD propagates from the pinned specs, and code
that must be manual (the shard-local kernels, the pod-manual gradients)
says so with ``jax.shard_map``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

DATA, MODEL, POD = "data", "model", "pod"

# TPU v5e hardware constants used by the roofline model.
PEAK_FLOPS_BF16 = 197e12      # FLOP/s per chip
HBM_BW = 819e9                # B/s per chip
ICI_BW = 50e9                 # B/s per link (intra-pod)
DCN_BW = 6.25e9               # B/s per host pair (inter-pod, ~50 Gbit)


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))



def make_production_mesh(*, multi_pod: bool = False, model_par: int = 16):
    """Production meshes. ``model_par`` re-factorizes the 256 chips/pod
    between the data and model axes (16×16 default; e.g. 32×8 lets yi-34b's
    56 heads shard — §Perf hillclimb). Chip count is invariant."""
    per_pod = 256
    assert per_pod % model_par == 0
    data = per_pod // model_par
    shape = (2, data, model_par) if multi_pod else (data, model_par)
    axes = (POD, DATA, MODEL) if multi_pod else (DATA, MODEL)
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Whatever devices exist locally (tests / CPU smoke runs)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), (DATA, MODEL))


def mesh_axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1
