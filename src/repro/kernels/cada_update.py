"""Fused CADA/AMSGrad server update — Pallas TPU kernel.

The paper's per-iteration hot spot is elementwise streaming over the full
parameter vector: the Adam/AMSGrad update (eqs. 2a-2c) plus CADA's two norm
reductions (the rule's RHS needs ||θ^{k+1}-θ^k||², the LHS needs
||fresh-stale||²). A naive jnp implementation makes ~9 separate HBM passes
over {θ, h, v, v̂, ∇}; both kernels below make exactly ONE pass, with the
scalar reductions accumulated in fp32.

TPU adaptation notes:
  * parameters are flattened and tiled into (BLOCK_ROWS, 128) VMEM blocks —
    lane dim 128, sublane a multiple of 8, so the VPU is fully utilized;
  * each block's partial sum is reduced to a scalar and added to an fp32
    accumulator that lives in SMEM (Mosaic cannot store scalars to VMEM):
    a whole-array (1, 1) output for the single-plane kernels, a whole-array
    (M,) output indexed by the worker row for the batched ones. The
    accumulator is initialized at the first block and revisited by every
    later one, so the block axis is declared ``arbitrary`` (sequential) —
    the standard Pallas accumulation pattern, no atomics needed;
  * scalar inputs (the learning rate) ride in SMEM too;
  * moments are carried in fp32 even when θ is bf16 (matches optim/adam.py).

Validated in ``interpret=True`` mode against ``ref.py`` (tests/test_kernels.py
sweeps shapes and dtypes) and compiled for a described TPU v5e at real
widths (tests/test_tpu_compile.py).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 256          # (256, 128) fp32 blocks = 128 KiB/operand in VMEM
BLOCK = BLOCK_ROWS * LANES

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)   # whole array, scalar memory


def _sequential(n_axes: int):
    """Every grid axis ``arbitrary``: the SMEM accumulators are revisited
    across the grid, so its steps must run in order on one core."""
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",) * n_axes)


def _amsgrad_kernel(theta_ref, h_ref, vhat_ref, grad_ref, lr_ref,
                    theta_out, h_out, vhat_out, sq_out,
                    *, b1: float, b2: float, eps: float):
    """One VMEM block of the fused AMSGrad/CADA update (paper eq. 2a-2c).

    Paper convention: v^{k+1} = β2·v̂^k + (1-β2)(∇^k)² (note v̂, not v), then
    v̂^{k+1} = max(v, v̂), and ε sits INSIDE the sqrt. Because (2b) reads v̂
    rather than v, the raw second moment v is a kernel-local temporary — the
    persistent optimizer state is only {h, v̂} (8P bytes, not 12P; bf16
    moment storage halves that again). Moments are dtype-parametric: math
    runs in fp32, the STORED (rounded) value drives the update — matching
    the per-leaf reference stream, so fp32 storage is bit-identical to the
    pre-parametric kernel and bf16 storage parity-matches the reference.
    """
    g = grad_ref[...].astype(jnp.float32)
    h32 = h_ref[...].astype(jnp.float32)
    vh32 = vhat_ref[...].astype(jnp.float32)
    h = (b1 * h32 + (1.0 - b1) * g).astype(h_out.dtype)
    v = b2 * vh32 + (1.0 - b2) * g * g
    vhat = jnp.maximum(v, vh32).astype(vhat_out.dtype)
    upd = (-lr_ref[0, 0] * h.astype(jnp.float32)
           / jnp.sqrt(eps + vhat.astype(jnp.float32)))

    theta = theta_ref[...]
    theta_out[...] = (theta.astype(jnp.float32) + upd).astype(theta.dtype)
    h_out[...] = h
    vhat_out[...] = vhat

    # ||θ^{k+1} − θ^k||² partial sum, accumulated across the sequential grid.
    blk = jnp.sum(upd * upd)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        sq_out[0, 0] = 0.0

    sq_out[0, 0] += blk


def fused_amsgrad_flat(theta, h, vhat, grad, lr, *, b1=0.9, b2=0.999,
                       eps=1e-8, interpret=False):
    """Fused update over pre-flattened (n_blocks*BLOCK,) buffers.

    Returns (theta', h', vhat', ||update||²). Moments keep their incoming
    storage dtype (fp32 or bf16 — see the kernel's dtype discipline).
    """
    n = theta.shape[0]
    assert n % BLOCK == 0, f"flat size {n} not a multiple of {BLOCK}"
    nb = n // BLOCK
    shape2d = (nb * BLOCK_ROWS, LANES)
    t2, h2, vh2, g2 = (a.reshape(shape2d) for a in (theta, h, vhat, grad))
    lr_arr = jnp.asarray(lr, jnp.float32).reshape(1, 1)

    spec = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    outs = pl.pallas_call(
        partial(_amsgrad_kernel, b1=b1, b2=b2, eps=eps),
        grid=(nb,),
        in_specs=[spec, spec, spec, spec, _SMEM],
        out_specs=(spec, spec, spec, _SMEM),
        out_shape=(
            jax.ShapeDtypeStruct(shape2d, theta.dtype),
            jax.ShapeDtypeStruct(shape2d, h.dtype),
            jax.ShapeDtypeStruct(shape2d, vhat.dtype),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ),
        compiler_params=_sequential(1),
        interpret=interpret,
    )(t2, h2, vh2, g2, lr_arr)
    t_new, h_new, vh_new, sq = outs
    return (t_new.reshape(n), h_new.reshape(n), vh_new.reshape(n), sq[0, 0])


def _batched_diff_sq_kernel(a_ref, b_ref, out_ref):
    """Partial Σ_j (a_mj − b_mj)² for ONE worker row, accumulated across the
    inner (sequential) block grid axis — all M CADA rule LHS norms in a
    single pass over the two (M, n) planes."""
    i = pl.program_id(0)
    d = a_ref[...].astype(jnp.float32) - b_ref[...].astype(jnp.float32)
    blk = jnp.sum(d * d)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[i] = 0.0

    out_ref[i] += blk


def batched_diff_sq_norm_flat(a, b, *, interpret=False):
    """(M,) per-worker ||a_m − b_m||² over (M, n) pre-flattened planes.

    The grid is (M, n/BLOCK) with the block axis innermost: the TPU grid is
    sequential, so each worker's entry of the (M,) SMEM accumulator is
    initialized at its first block and revisited — the same pattern as the
    unbatched kernels, just with a second grid axis for the worker rows.
    """
    m, n = a.shape
    assert n % BLOCK == 0, f"flat width {n} not a multiple of {BLOCK}"
    nb = n // BLOCK
    shape3d = (m, nb * BLOCK_ROWS, LANES)
    spec = pl.BlockSpec((None, BLOCK_ROWS, LANES), lambda i, j: (i, j, 0))
    return pl.pallas_call(
        _batched_diff_sq_kernel,
        grid=(m, nb),
        in_specs=[spec, spec],
        out_specs=_SMEM,
        out_shape=jax.ShapeDtypeStruct((m,), jnp.float32),
        compiler_params=_sequential(2),
        interpret=interpret,
    )(a.reshape(shape3d), b.reshape(shape3d))


def _batched_sq_kernel(a_ref, out_ref):
    """Partial Σ_j a_mj² for one worker row (single-operand variant)."""
    i = pl.program_id(0)
    v = a_ref[...].astype(jnp.float32)
    blk = jnp.sum(v * v)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[i] = 0.0

    out_ref[i] += blk


def batched_sq_norm_flat(a, *, interpret=False):
    """(M,) per-worker ||a_m||² over an (M, n) pre-flattened plane."""
    m, n = a.shape
    assert n % BLOCK == 0, f"flat width {n} not a multiple of {BLOCK}"
    nb = n // BLOCK
    shape3d = (m, nb * BLOCK_ROWS, LANES)
    spec = pl.BlockSpec((None, BLOCK_ROWS, LANES), lambda i, j: (i, j, 0))
    return pl.pallas_call(
        _batched_sq_kernel,
        grid=(m, nb),
        in_specs=[spec],
        out_specs=_SMEM,
        out_shape=jax.ShapeDtypeStruct((m,), jnp.float32),
        compiler_params=_sequential(2),
        interpret=interpret,
    )(a.reshape(shape3d))


def _diff_sq_kernel(a_ref, b_ref, out_ref):
    """Partial Σ (a − b)² — the CADA rule LHS, one fused pass."""
    d = a_ref[...].astype(jnp.float32) - b_ref[...].astype(jnp.float32)
    blk = jnp.sum(d * d)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[0, 0] = 0.0

    out_ref[0, 0] += blk


def diff_sq_norm_flat(a, b, *, interpret=False):
    """||a − b||² over pre-flattened buffers (rule LHS, eqs. 7/10)."""
    n = a.shape[0]
    assert n % BLOCK == 0, f"flat size {n} not a multiple of {BLOCK}"
    nb = n // BLOCK
    shape2d = (nb * BLOCK_ROWS, LANES)
    spec = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        _diff_sq_kernel,
        grid=(nb,),
        in_specs=[spec, spec],
        out_specs=_SMEM,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        compiler_params=_sequential(1),
        interpret=interpret,
    )(a.reshape(shape2d), b.reshape(shape2d))
    return out[0, 0]


ROW_MEAN_BLOCK_BYTES = 1 << 20  # fp32 bytes of one (rows, cols) plane block


def descending_row_sum(row, rows: int):
    """Σ_i row(i) over ``rows`` static rows, in DESCENDING order from +0.0:
    the order that makes all-zero rows exact no-ops (see
    ``ops.eq3_row_mean``). The +0.0 start is the first row with −0.0
    mapped to +0.0, since XLA folds an explicit ``0.0 + x`` to ``x``; a
    single row is taken as it is, which is what that fold gives."""
    acc = row(rows - 1)
    if rows > 1:
        acc = jnp.where(acc == 0.0, 0.0, acc)
    for i in range(rows - 2, -1, -1):
        acc = acc + row(i)
    return acc


def _row_mean_kernel(plane_ref, *refs, rows: int, m_total: int):
    """Eq. (3)'s aggregate over one column block of the (rows, n) plane,
    from static row slices of the VMEM block; with a base block, the
    result is added to it."""
    *base, out_ref = refs
    acc = descending_row_sum(lambda i: plane_ref[i:i + 1, :], rows) / m_total
    out_ref[...] = base[0][...] + acc if base else acc


def row_mean_flat(plane, m_total: int, base=None, *, interpret=False):
    """(n,) ``base + Σ_rows(plane) / m_total`` over an fp32 (rows, n)
    plane in one pass, rows summed in descending order.

    The plane is read in its own (rows, cols) layout — no reshape, so the
    compiler makes no relayout copy of a few-row plane — and the base is
    updated in place. A partial last column block is masked on the write.
    """
    rows, n = plane.shape
    cols = max(LANES, ROW_MEAN_BLOCK_BYTES // (4 * rows) // LANES * LANES)
    spec = pl.BlockSpec((1, cols), lambda j: (0, j))
    operands = [plane] + ([base.reshape(1, n)] if base is not None else [])
    out = pl.pallas_call(
        partial(_row_mean_kernel, rows=rows, m_total=m_total),
        grid=(pl.cdiv(n, cols),),
        in_specs=[pl.BlockSpec((rows, cols), lambda j: (0, j))]
        + [spec] * (len(operands) - 1),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        input_output_aliases={1: 0} if base is not None else {},
        interpret=interpret,
    )(*operands)
    return out.reshape(n)
