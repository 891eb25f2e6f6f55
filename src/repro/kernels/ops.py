"""Jit'd public wrappers around the Pallas kernels.

Kernel-mode routing (the ``interpret`` flag on every flat op):

  * ``None`` (default) — on TPU, compile the Pallas kernel with Mosaic;
    elsewhere use the FUSED FLAT JNP fallback (same math on the same flat
    buffers, fused by XLA) so the hot paths and the test suite stay fast on
    CPU;
  * ``True``  — run the Pallas kernel in interpret mode (the kernel body
    executes as traced jnp, bit-faithful validation of the BlockSpec
    tiling);
  * ``False`` — force the compiled Pallas kernel.

The wrappers also own the BLOCK padding: arbitrary flat lengths are padded
with zeros up to whole kernel blocks and sliced back, so every pytree —
logreg through the LM path — takes the fused route (zero-padded gradients
leave zero moments and a zero update, so reductions are unaffected).

Sharded flat planes (the ``shard`` flag on the flat ops, a static
``distributed.sharding.FlatSharding``): the same kernels run SHARD-LOCAL
under a shard_map that is manual over every mesh axis — each device
streams only its ``n_flat / shards`` slice (or its rows of the (M, n_flat)
planes) — and the scalar reductions (‖Δθ‖², the (M,) rule-LHS norms) are
completed with ONE psum of fp32 partials. The only cross-device bytes the
state math ever pays are those O(M) scalars; no plane is gathered.

``fused_cada_update`` is the pytree-level entry point used by the optimizer:
it flattens the parameter pytree into one padded fp32 stream, runs the fused
update, and scatters back — giving the one-HBM-pass optimizer step plus the
CADA rule's ||Δθ||² for free.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import cada_update as _cu
from repro.kernels import ref as _ref
from repro.kernels import ssm_scan as _ss


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _use_pallas(interpret) -> tuple[bool, bool]:
    """Resolve the 3-way ``interpret`` flag -> (use_pallas, interpret)."""
    if interpret is None:
        return jax.default_backend() == "tpu", False
    return True, bool(interpret)


def _pad_flat(arrs, block=_cu.BLOCK):
    """Zero-pad same-length flat buffers to a whole number of blocks."""
    n = arrs[0].shape[0]
    pad = (-n) % block
    if pad == 0:
        return arrs, n
    return [jnp.pad(a, ((0, pad),)) for a in arrs], n


def _pad_plane(a, block=_cu.BLOCK):
    """Zero-pad the flat axis of an (M, n) plane to whole blocks."""
    pad = (-a.shape[1]) % block
    return jnp.pad(a, ((0, 0), (0, pad))) if pad else a


def _shard_map(f, shard, in_specs, out_specs):
    """shard_map MANUAL over every axis of the plane's mesh: a Mosaic kernel
    cannot be partitioned automatically, so no axis may stay auto around
    it. Axes a spec leaves out are replicated, as the planes are."""
    return jax.shard_map(f, mesh=shard.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ------------------------------------------------------------------ flat ops

@partial(jax.jit, static_argnames=("b1", "b2", "eps", "interpret", "shard"))
def fused_amsgrad_flat(theta, h, vhat, grad, lr, *, b1=0.9, b2=0.999,
                       eps=1e-8, interpret=None, shard=None):
    """Fused AMSGrad/CADA step over arbitrary-length flat buffers.

    Returns (theta', h', vhat', ||update||²); moments keep their incoming
    storage dtype (fp32 or bf16 — see kernels/cada_update.py).

    ``shard`` (static FlatSharding, optional): run SHARD-LOCAL — manual
    shard_map, each device fusing its own
    ``n_flat / shards`` slice in one pass, with a single psum of the fp32
    ‖Δθ‖² partials. The global result is identical (the padding discipline
    makes every local slice self-contained).
    """
    if shard is not None and shard.axes:
        from jax.sharding import PartitionSpec as P
        spec = shard.server_spec()

        def local(t, hh, vh, g, lr_):
            t2, h2, vh2, sq = fused_amsgrad_flat(
                t, hh, vh, g, lr_, b1=b1, b2=b2, eps=eps,
                interpret=interpret)
            return t2, h2, vh2, jax.lax.psum(sq, shard.axes)

        return _shard_map(local, shard, (spec,) * 4 + (P(),),
                          (spec, spec, spec, P()))(
            theta, h, vhat, grad, jnp.asarray(lr, jnp.float32))
    pallas, interpret = _use_pallas(interpret)
    if not pallas:
        return _ref.amsgrad_ref(theta, h, vhat, grad, lr, b1=b1, b2=b2,
                                eps=eps)
    (t, hh, vh, g), n = _pad_flat([theta, h, vhat, grad])
    t2, h2, vh2, sq = _cu.fused_amsgrad_flat(t, hh, vh, g, lr, b1=b1, b2=b2,
                                             eps=eps, interpret=interpret)
    return t2[:n], h2[:n], vh2[:n], sq


@partial(jax.jit, static_argnames=("interpret",))
def diff_sq_norm_flat(a, b, *, interpret=None):
    pallas, interpret = _use_pallas(interpret)
    if not pallas:
        return _ref.diff_sq_norm_ref(a, b)
    (ap, bp), _ = _pad_flat([a, b])
    return _cu.diff_sq_norm_flat(ap, bp, interpret=interpret)


@partial(jax.jit, static_argnames=("m_total", "shard", "interpret"))
def eq3_row_mean(plane, m_total, base=None, *, shard=None, interpret=None):
    """Eq. (3) server aggregate increment: Σ_rows(plane) / m_total, added
    to ``base`` (the server's ∇̄, in fp32) when one is given.

    The row reduction is an ORDER-FIXED sequential accumulation over
    rows in DESCENDING row order, not XLA's tree reduction: a chain of
    static row slices, unrolled at trace time (the row count is a static
    shape), so it is one elementwise pass over the plane — on the TPU the
    Pallas kernel ``row_mean_flat``, elsewhere the same chain in jnp.  A
    fixed sequential order makes the result invariant to dropping
    all-zero rows: a masked dense ``(M, n)`` wire plane and the gathered
    ``(C, n)`` cohort plane holding only its nonzero rows (in ascending
    worker order) produce BIT-IDENTICAL fp32 aggregates, which is what
    lets the cohort-virtualized worker plane stay a drop-in for the dense
    plane.  (+0.0 addends are exact no-ops: the sum starts from +0.0 and
    IEEE-754 addition can only reach −0.0 from two −0.0 operands, so
    skipping zero rows never changes a bit; ``descending_row_sum`` says
    how the +0.0 start is written.)  Pass ``m_total`` = the FULL worker
    count M even when ``plane`` has only C cohort rows.

    ``shard``: under a sharded worker axis a cross-device sequential
    order is not expressible — fall back to the tree reduction (the
    sharded trainer plane is never the cohort parity oracle).
    """
    plane = plane.astype(jnp.float32)
    if shard is None:
        pallas, interpret = _use_pallas(interpret)
        if pallas:
            return _cu.row_mean_flat(plane, m_total, base,
                                     interpret=interpret)
        mean = _cu.descending_row_sum(lambda i: plane[i],
                                      plane.shape[0]) / m_total
    else:
        mean = jnp.sum(plane, axis=0) / m_total
    return mean if base is None else base + mean


@partial(jax.jit, static_argnames=("interpret", "shard"))
def batched_diff_sq_norm(a, b, *, interpret=None, shard=None):
    """(M,) per-worker ||a_m − b_m||² over (M, n) planes — the CADA rule
    LHS for all M workers in one pass (fp32 accumulate).

    The leading axis is polymorphic: a cohort-sized ``(C, n)`` plane (only
    the sampled workers' rows resident on device) takes the same kernel —
    per-row reductions never mix rows, so cohort rows are bit-identical
    to the same rows of the dense ``(M, n)`` pass.

    ``b`` is whatever second-gradient plane the eval dispatch produced —
    gathered per-worker rows, the stacked fused eval's second half, or
    the GROUPED plane scattered by stale-ring slot
    (``flat.grouped_second_plane``) — all land here as a dense (M, n)
    operand, so the LHS needs no re-gather and no grouping awareness.

    ``shard`` (static FlatSharding, optional): shard-local form — each
    device sweeps only its own rows (the worker axis) and its slice of the
    plane's columns, finishing the per-row partials with one psum over the
    column axes. Rows stay whole per device otherwise.
    """
    if shard is not None:
        from jax.sharding import PartitionSpec as P
        cols = shard.col_axes
        in_spec = shard.worker_spec()

        def local(al, bl):
            r = batched_diff_sq_norm(al, bl, interpret=interpret)
            return jax.lax.psum(r, cols) if cols else r

        return _shard_map(local, shard, (in_spec, in_spec),
                          P(shard.waxis))(a, b)
    pallas, interpret = _use_pallas(interpret)
    if not pallas:
        d = a.astype(jnp.float32) - b.astype(jnp.float32)
        return jnp.sum(d * d, axis=1)
    ap, bp = (_pad_plane(x) for x in (a, b))
    return _cu.batched_diff_sq_norm_flat(ap, bp, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret", "shard"))
def batched_sq_norm(a, *, interpret=None, shard=None):
    """(M,) per-worker ||a_m||² over an (M, n) plane (``shard`` as in
    :func:`batched_diff_sq_norm`)."""
    if shard is not None:
        from jax.sharding import PartitionSpec as P
        cols = shard.col_axes

        def local(al):
            r = batched_sq_norm(al, interpret=interpret)
            return jax.lax.psum(r, cols) if cols else r

        return _shard_map(local, shard, (shard.worker_spec(),),
                          P(shard.waxis))(a)
    pallas, interpret = _use_pallas(interpret)
    if not pallas:
        v = a.astype(jnp.float32)
        return jnp.sum(v * v, axis=1)
    return _cu.batched_sq_norm_flat(_pad_plane(a), interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "dblk", "interpret"))
def selective_scan(dt, x, a, b, c, *, chunk=_ss.DEFAULT_CHUNK,
                   dblk=_ss.DEFAULT_DBLK, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return _ss.selective_scan(dt, x, a, b, c, chunk=chunk, dblk=dblk,
                              interpret=interpret)


@partial(jax.jit, static_argnames=("window", "q_blk", "kv_blk",
                                   "interpret"))
def flash_attention(q, k, v, *, window=0, q_blk=None, kv_blk=None,
                    interpret=None):
    """GQA flash attention via the Pallas kernel.

    q (B, S, Hq, hd); k/v (B, S, Hkv, hd). Each Q head is paired with its
    KV head and flattened onto the kernel's G axis.
    """
    from repro.kernels import flash_attention as _fa
    if interpret is None:
        interpret = _default_interpret()
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    grp = hq // hkv
    qg = q.transpose(0, 2, 1, 3).reshape(b * hq, s, hd)
    kg = jnp.repeat(k.transpose(0, 2, 1, 3), grp, axis=1).reshape(
        b * hq, s, hd)
    vg = jnp.repeat(v.transpose(0, 2, 1, 3), grp, axis=1).reshape(
        b * hq, s, hd)
    kw = {}
    if q_blk:
        kw["q_blk"] = q_blk
    if kv_blk:
        kw["kv_blk"] = kv_blk
    o = _fa.flash_attention_kernel(qg, kg, vg, window=window,
                                   interpret=interpret, **kw)
    return o.reshape(b, hq, s, hd).transpose(0, 2, 1, 3)


# --------------------------------------------------------------- pytree ops

def _flatten_padded(tree, dtype, block=1024):
    """Concat all leaves (as ``dtype``) into one flat buffer padded to full
    VPU tiles. Returns (flat, unflatten_fn). Kernel-block padding happens
    inside the flat wrappers above, so small pytrees stay small here."""
    leaves, treedef = jax.tree.flatten(tree)
    sizes = [l.size for l in leaves]
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(dtype) for l in leaves])
    pad = (-flat.size) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))

    def unflatten(buf, out_dtypes=None):
        out_dtypes = out_dtypes or dtypes
        outs, off = [], 0
        for sz, shp, dt in zip(sizes, shapes, out_dtypes):
            outs.append(buf[off:off + sz].reshape(shp).astype(dt))
            off += sz
        return jax.tree.unflatten(treedef, outs)

    return flat, unflatten


def fused_cada_update(params, h, vhat, grads, lr, *, b1=0.9, b2=0.999,
                      eps=1e-8, interpret=None):
    """Pytree-level fused CADA/AMSGrad step.

    Returns (params', h', vhat', ||θ'−θ||²). Padding lanes carry zero
    gradients, so their moments stay exactly zero and the update there is 0 —
    the norm is unaffected (eps > 0).
    """
    pf, unflat_p = _flatten_padded(params, jnp.float32)
    hf, unflat_m = _flatten_padded(h, jnp.float32)
    vhf, _ = _flatten_padded(vhat, jnp.float32)
    gf, _ = _flatten_padded(grads, jnp.float32)
    pt, ht, vht, sq = fused_amsgrad_flat(
        pf, hf, vhf, gf, lr, b1=b1, b2=b2, eps=eps, interpret=interpret)
    f32 = [jnp.float32] * len(jax.tree.leaves(h))
    p_dtypes = [l.dtype for l in jax.tree.leaves(params)]
    return (unflat_p(pt, p_dtypes), unflat_m(ht, f32),
            unflat_m(vht, f32), sq)


def diff_sq_norm(tree_a, tree_b, *, interpret=None):
    """||a − b||² over two same-structure pytrees (CADA rule LHS)."""
    af, _ = _flatten_padded(tree_a, jnp.float32)
    bf, _ = _flatten_padded(tree_b, jnp.float32)
    return diff_sq_norm_flat(af, bf, interpret=interpret)
