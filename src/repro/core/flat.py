"""Flat-buffer state plane: Algorithm 1's per-iteration math on contiguous
buffers instead of per-leaf pytrees.

Motivation (§Perf): the reference ``core/comm.py::comm_round`` and the
per-leaf jnp AMSGrad stream walk the parameter pytree ~15 times per
iteration — every ``tree_map`` is one more sweep over HBM (or, on CPU, one
more dispatched kernel inside the scanned step). This module packs the
gradient-shaped state ONCE into padded contiguous buffers and re-expresses
the whole communication round as a handful of whole-buffer ops:

  * :class:`FlatLayout` — a static description of a pytree's flat layout
    (per-leaf offsets/sizes/shapes/dtypes, total padded length ``n_flat``)
    with exact ``pack``/``unpack`` round-tripping, including an (M, n_flat)
    per-worker plane for M-leading trees. The layout is SHARDING-AWARE:
    built with ``shards=S``, ``n_flat`` is padded to a multiple of
    ``S · align`` so the flat axis splits into S equal contiguous shards —
    exactly the split a ``PartitionSpec`` over the state-shard mesh axes
    produces — and :meth:`shard_split`/:meth:`shard_merge` round-trip the
    per-shard view bit-exactly;
  * :class:`FlatCommState` — the Algorithm-1 communication state with
    ``nabla`` as one (n_flat,) buffer and every per-worker tree as one
    (M, n_flat) plane;
  * :func:`flat_comm_round` — the same Algorithm-1 round as
    ``comm.comm_round`` (lines 4-15), but the fresh−stale delta, the mask
    merge, the eq. (3) innovation aggregation and the rule LHS norms are
    single flat ops (the LHS norms via the batched Pallas kernel on TPU, a
    fused flat jnp fallback elsewhere — see ``kernels/ops.py``).

Rule-specific behaviour stays with the :mod:`repro.core.comm` strategy
objects — each strategy carries flat-plane hooks (``flat_lhs``,
``flat_post_upload``, ...) next to its reference pytree hooks, and the
fused-vs-reference engine parity test keeps the two in lockstep.

Model math is untouched: parameters remain a pytree for the loss/grad
evaluation, and the layout is the single conversion point between the two
worlds (gradients are packed once per iteration, right after ``vgrad``).
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantize import topk_count, topk_threshold_mask
from repro.kernels import ops as kops

# Minimal flat-buffer alignment. The Pallas wrappers in kernels/ops.py
# re-pad to whole kernel blocks on demand, so the layout itself stays lean:
# on CPU a (M, n_flat) plane carries almost no padding waste even for toy
# models (logreg: 46 -> 48), while TPU kernels see block-aligned buffers
# after the wrapper's pad.
PAD_ALIGN = 8


# ------------------------------------------------------------------- layout

@dataclass(frozen=True)
class FlatLayout:
    """Static flat layout of a pytree: one contiguous padded buffer.

    Hashable and comparable, so it can be closed over by jitted steps and
    compared across engine/trainer instances. ``n`` is the true scalar
    count, ``n_flat`` the padded buffer length (a multiple of both
    ``align`` and ``shards``); padding lanes are identically zero through
    every op in this module. ``shards`` is the state-shard count of the
    target mesh (1 = unsharded): shard ``s`` owns the contiguous slice
    ``[s·shard_len, (s+1)·shard_len)`` — the same equal contiguous split a
    ``PartitionSpec`` over the state-shard axes gives each device, so the
    layout, the sharding specs and the shard-local kernels all agree on
    where every parameter lives.
    """
    treedef: Any
    shapes: tuple
    dtypes: tuple
    sizes: tuple
    offsets: tuple
    n: int
    n_flat: int
    shards: int = 1

    @property
    def shard_len(self) -> int:
        """Flat entries owned by one state shard (``n_flat / shards``)."""
        return self.n_flat // self.shards

    # ---- per-shard conversions
    def shard_split(self, buf: jnp.ndarray) -> jnp.ndarray:
        """(..., n_flat) buffer -> (..., shards, shard_len) per-shard view.

        A pure reshape (shard s is the contiguous slice it owns), so
        ``shard_merge(shard_split(buf)) == buf`` bit-exactly — the
        invariant the checkpoint resharding path relies on.
        """
        return buf.reshape(buf.shape[:-1] + (self.shards, self.shard_len))

    def shard_merge(self, parts: jnp.ndarray) -> jnp.ndarray:
        """(..., shards, shard_len) per-shard view -> (..., n_flat)."""
        return parts.reshape(parts.shape[:-2] + (self.n_flat,))

    # ---- conversions: every move between pytrees and flat planes is named
    # ``cada.pack`` on the device, wherever it is called from
    def pack(self, tree, dtype=jnp.float32) -> jnp.ndarray:
        """Pytree -> (n_flat,) buffer in ``dtype`` (zero-padded tail)."""
        with jax.named_scope("cada.pack"):
            leaves = jax.tree.leaves(tree)
            flat = jnp.concatenate(
                [jnp.ravel(l).astype(dtype) for l in leaves])
            if self.n_flat > self.n:
                flat = jnp.pad(flat, (0, self.n_flat - self.n))
            return flat

    def pack_worker(self, tree, dtype=jnp.float32) -> jnp.ndarray:
        """M-leading pytree -> (M, n_flat) plane in ``dtype``."""
        with jax.named_scope("cada.pack"):
            leaves = jax.tree.leaves(tree)
            m = leaves[0].shape[0]
            flat = jnp.concatenate(
                [l.reshape(m, -1).astype(dtype) for l in leaves], axis=1)
            if self.n_flat > self.n:
                flat = jnp.pad(flat, ((0, 0), (0, self.n_flat - self.n)))
            return flat

    def unpack(self, buf, dtypes=None):
        """(n_flat,) buffer -> pytree (leaves cast to the layout dtypes)."""
        dtypes = dtypes or self.dtypes
        with jax.named_scope("cada.pack"):
            outs = [buf[o:o + s].reshape(shp).astype(dt)
                    for o, s, shp, dt in zip(self.offsets, self.sizes,
                                             self.shapes, dtypes)]
        return jax.tree.unflatten(self.treedef, outs)

    def unpack_worker(self, buf, dtypes=None):
        """(M, n_flat) plane -> M-leading pytree."""
        dtypes = dtypes or self.dtypes
        m = buf.shape[0]
        with jax.named_scope("cada.pack"):
            outs = [buf[:, o:o + s].reshape((m,) + shp).astype(dt)
                    for o, s, shp, dt in zip(self.offsets, self.sizes,
                                             self.shapes, dtypes)]
        return jax.tree.unflatten(self.treedef, outs)

    # ---- dtype discipline
    @property
    def all_f32(self) -> bool:
        return all(dt == np.dtype(np.float32) for dt in self.dtypes)

    def cast_roundtrip(self, buf: jnp.ndarray) -> jnp.ndarray:
        """Round-trip a (n_flat,) fp32 buffer through the per-leaf storage
        dtypes, so ``buf == pack(unpack(buf))`` holds exactly even for
        reduced-precision leaves. No-op for all-fp32 layouts (static)."""
        if self.all_f32:
            return buf
        with jax.named_scope("cada.pack"):
            parts = [buf[o:o + s].astype(dt).astype(buf.dtype)
                     for o, s, dt in zip(self.offsets, self.sizes,
                                         self.dtypes)]
            if self.n_flat > self.n:
                parts.append(buf[self.n:])
            return jnp.concatenate(parts)


def layout_of(tree, align: int | None = None, shards: int = 1) -> FlatLayout:
    """Build the static :class:`FlatLayout` of ``tree`` (arrays or
    ShapeDtypeStructs both work — only shapes/dtypes are read).

    ``shards`` is the state-shard count the flat axis must divide into
    (``distributed.trainer.flat_state_shards`` resolves it from the mesh);
    ``n_flat`` is padded to a multiple of ``align · shards`` so every shard
    gets an equal, ``align``-aligned contiguous slice. ``shards=1``
    reproduces the unsharded layout exactly (same ``n_flat`` as before).
    """
    if align is None:
        align = PAD_ALIGN
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    leaves, treedef = jax.tree.flatten(tree)
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(np.dtype(l.dtype) for l in leaves)
    sizes = tuple(int(np.prod(s, dtype=np.int64)) if s else 1
                  for s in shapes)
    offsets, off = [], 0
    for s in sizes:
        offsets.append(off)
        off += s
    n = off
    step = align * shards
    n_flat = n + ((-n) % step)
    return FlatLayout(treedef=treedef, shapes=shapes, dtypes=dtypes,
                      sizes=sizes, offsets=tuple(offsets), n=n,
                      n_flat=max(n_flat, step), shards=shards)


def spec_dim(axes: tuple) -> Any:
    """One PartitionSpec DIMENSION entry for a tuple of mesh axes:
    ``()`` -> None (replicated), one axis -> its name, several -> the
    tuple (sharded over their product). The single home of the rule, used
    by the flat-plane spec builders here and in distributed/sharding.py."""
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else tuple(axes)


def _segment_ids(layout: FlatLayout) -> np.ndarray:
    """(n_flat,) int32 leaf-segment id per buffer position; the padding
    tail (if any) is its own trailing segment ``len(sizes)``. Static —
    computed from the layout at trace time."""
    ids = np.full((layout.n_flat,), len(layout.sizes), np.int32)
    for i, (o, s) in enumerate(zip(layout.offsets, layout.sizes)):
        ids[o:o + s] = i
    return ids


def per_worker_quantize_dequantize_flat(layout: FlatLayout, buf, bits: int):
    """Flat-plane twin of ``quantize.per_worker_quantize_dequantize``:
    b-bit symmetric uniform round-trip with one max-abs scale per
    (worker, leaf-segment) — bit-identical to the pytree version, since the
    scales are exact maxima over the same entries.

    Vectorized over segments: ONE segment-max sweep computes every
    (worker, leaf) scale and one gather broadcasts them back, instead of a
    Python loop materializing a slice + concatenate per leaf (the loop cost
    scaled with the number of leaves — LM pytrees have hundreds). The
    padding tail passes through untouched (max is exact, so bit-equality
    with the pytree form is preserved)."""
    if bits <= 0 or bits >= 32:
        return buf
    levels = float(2 ** (bits - 1) - 1)
    n_seg = len(layout.sizes)
    seg = jnp.asarray(_segment_ids(layout))
    xf = buf.astype(jnp.float32)
    # (n_seg+1, M) per-segment max-abs; empty segments are never gathered
    # into a non-pad position, so their -inf identity is harmless.
    seg_max = jax.ops.segment_max(jnp.abs(xf).T, seg, num_segments=n_seg + 1,
                                  indices_are_sorted=True)
    scale = jnp.maximum(seg_max, 1e-12)[seg].T          # (M, n_flat)
    q = jnp.round(xf / scale * levels)
    deq = (q * scale / levels).astype(buf.dtype)
    if layout.n_flat > layout.n:
        deq = jnp.where((seg < n_seg)[None, :], deq, buf)
    return deq


def per_worker_topk_sparsify_flat(layout: FlatLayout, buf, frac: float):
    """Flat-plane twin of ``quantize.per_worker_topk_sparsify``: keep
    EXACTLY the top-⌈frac·size⌉ largest-|x| entries per (worker,
    leaf-segment) (ties break toward the lower index — see
    ``topk_threshold_mask``), zero the rest — bit-identical to the pytree
    form (same selection over the same entries in the same order). Top-k
    runs per segment: segments are ragged (one k per segment), and
    bit-equality with the pytree sparsifier is what the parity gates pin,
    so the per-segment loop is the deliberate trade-off here (unlike the
    quantizer above, whose max-scales vectorize exactly). The padding
    tail passes through untouched."""
    if frac >= 1.0:
        return buf
    parts = []
    for o, s in zip(layout.offsets, layout.sizes):
        seg = buf[:, o:o + s]
        mask = topk_threshold_mask(seg.astype(jnp.float32),
                                   topk_count(s, frac))
        parts.append(seg * mask)
    if layout.n_flat > layout.n:
        parts.append(buf[:, layout.n:])
    return jnp.concatenate(parts, axis=1)


def per_worker_topk_extract_flat(layout: FlatLayout, plane, frac: float):
    """Extract the top-k SPARSE WIRE from an (M, n_flat) sparsified plane:
    ((M, K) fp32 values, (M, K) int32 global flat positions) with
    K = Σ_seg ⌈frac·size_seg⌉ — a fixed-size payload, so it can ride a
    collective as-is. Applied to the compressor's output — whose support
    is exactly k entries per segment (``topk_threshold_mask`` keeps
    exactly k, ties index-broken) — the pair reconstructs the dense plane
    bit-exactly via :func:`sparse_rows_to_dense`; the parity test pins
    that equality."""
    vparts, iparts = [], []
    for o, s in zip(layout.offsets, layout.sizes):
        seg = plane[:, o:o + s].astype(jnp.float32)
        k = topk_count(s, frac)
        _, idx = jax.lax.top_k(jnp.abs(seg), k)
        vparts.append(jnp.take_along_axis(seg, idx, axis=1))
        iparts.append(idx.astype(jnp.int32) + o)
    return jnp.concatenate(vparts, axis=1), jnp.concatenate(iparts, axis=1)


def sparse_rows_to_dense(idx, vals, n_flat: int) -> jnp.ndarray:
    """Scatter per-worker (values, indices) wire pairs back onto a dense
    (M, n_flat) plane (the server side of the sparse collective). Indices
    are distinct per row (disjoint per-segment top-k), so add == set."""
    m = vals.shape[0]
    rows = jnp.arange(m)[:, None]
    return jnp.zeros((m, n_flat), vals.dtype).at[rows, idx].add(vals)


# ----------------------------------------------------- local-steps cadence

def batch_has_local_axis(rule, local_steps) -> bool:
    """STATIC: does a delta-payload round's batch lead with the H axis?

    The payload/cadence contract: a delta-payload rule's batch is
    (H, M, b, ...) whenever the rule runs more than one local step
    (``rule.local_steps > 1``) or an explicit per-round schedule is passed
    (``local_steps is not None`` — the sim's adaptive path, which pads the
    batch to the schedule's cap). With the default H = 1 and no schedule
    the batch keeps the plain (M, b, ...) form every gradient-payload path
    uses — so a delta rule at H = 1 drops into any existing engine/sweep
    unchanged.
    """
    return rule.local_steps > 1 or local_steps is not None


def local_steps_vector(rule, m: int, batch_h, local_steps) -> jnp.ndarray:
    """(M,) int32 per-worker local-step counts of one delta-payload round.

    ``batch_h`` leads with the (static) local-steps axis H — its length is
    the padding bound; ``local_steps`` (None | scalar | (M,)) selects how
    many of those H steps each worker actually runs this round (None = all
    H, the fixed-cadence case; the sim's adaptive schedule passes a
    per-worker vector, clipped here into [1, H] so a stale schedule can
    never index past the batch)."""
    h_max = jax.tree.leaves(batch_h)[0].shape[0]
    if local_steps is None:
        return jnp.full((m,), h_max, jnp.int32)
    h = jnp.asarray(local_steps, jnp.int32)
    return jnp.clip(jnp.broadcast_to(h, (m,)), 1, h_max)


# -------------------------------------------------------------- comm state

class FlatCommState(NamedTuple):
    """Algorithm-1 communication state on the flat plane.

    Mirrors ``comm.CommState`` field-for-field; gradient-shaped trees are
    single buffers ((n_flat,) for ∇, (M, n_flat) per-worker planes), so the
    round below touches each exactly once per iteration.
    """
    nabla: jnp.ndarray        # (n_flat,) storage dtype
    worker_grads: jnp.ndarray  # (M, n_flat) storage dtype
    staleness: jnp.ndarray    # (M,) int32
    diff_hist: jnp.ndarray    # (d_max,) fp32 RHS ring buffer
    extras: dict              # strategy-owned flat slices


class FlatCommContext(NamedTuple):
    """What a strategy's flat hooks may consult. ``fresh`` is the packed
    (M, n_flat) fp32 fresh-gradient plane; ``second`` the packed gradients
    at the strategy's second evaluation points (None if it has none);
    ``shard`` the static flat-plane sharding descriptor
    (distributed.sharding.FlatSharding) or None — strategies pass it
    through to the kernels so the batched LHS norms run shard-local with
    one psum instead of resharding whole planes."""
    layout: FlatLayout
    params: Any               # θ^k pytree (model form)
    params_flat: jnp.ndarray  # θ^k packed, fp32
    batch: Any
    fresh: jnp.ndarray
    second: jnp.ndarray | None
    comm: FlatCommState
    step: jnp.ndarray
    m: int
    interpret: Any            # kernel-mode override for kernels/ops.py
    shard: Any = None         # FlatSharding | None (static)
    participation: Any = None  # (M,) bool round-participation mask | None
    # Cohort-virtualized plane (flat_cohort_round): the (C,) int32 sorted
    # global worker ids whose rows are resident this round, or None on the
    # dense plane. When set, ``m`` is C, every per-worker plane in
    # ctx/extras pooled by the strategy has C rows, and full-length (M,)
    # server-resident extras (avp periods, cada2 slots) must be indexed by
    # it — see each strategy's flat hooks.
    cohort: Any = None


class FlatCommRoundResult(NamedTuple):
    losses: jnp.ndarray
    comm: FlatCommState       # diff_hist NOT yet updated (record_progress)
    upload: jnp.ndarray
    metrics: dict


def init_flat_comm_state(strategy, layout: FlatLayout, params, m: int,
                         grad_dtype=jnp.float32,
                         params_flat=None) -> FlatCommState:
    """Fresh flat CommState: τ_m starts at D so iteration 0 uploads."""
    r = strategy.rule
    if params_flat is None:
        params_flat = layout.pack(params)
    return FlatCommState(
        nabla=jnp.zeros((layout.n_flat,), grad_dtype),
        worker_grads=jnp.zeros((m, layout.n_flat), grad_dtype),
        staleness=jnp.full((m,), r.max_delay, jnp.int32),
        diff_hist=jnp.zeros((r.d_max,), jnp.float32),
        extras=strategy.init_flat_extras(layout, params, params_flat, m,
                                         grad_dtype),
    )


def flat_comm_state_specs(strategy, param_spec, worker_param_spec,
                          waxis: str, P, state_axes: tuple = (),
                          col_axes: tuple = ()) -> FlatCommState:
    """PartitionSpec tree matching :func:`init_flat_comm_state` — the
    gradient planes need exactly two spec shapes (server (n_flat,) buffers
    sharded over ``state_axes``, worker-leading (M, n_flat) planes sharded
    worker-axis × ``col_axes``); parameter-shaped extras reuse the param
    specs. ``col_axes`` is ``state_axes`` minus the worker axis (an axis
    may not repeat within one spec)."""
    return FlatCommState(
        nabla=P(spec_dim(state_axes)),
        worker_grads=P(waxis, spec_dim(col_axes)),
        staleness=P(None),
        diff_hist=P(None),
        extras=strategy.flat_extras_specs(param_spec, worker_param_spec,
                                          waxis, P, col_axes=col_axes),
    )


# ------------------------------------------------------------ two-point eval

def stacked_two_point_eval(layout: FlatLayout, params, pts, batch, m: int,
                           vgrad_per):
    """Fresh + second gradients from ONE vmapped call, WITHOUT copying the
    batch: the 2-way eval axis is a broadcast vmap level (in_axes=None for
    the batch), not a doubled (2M,)-leading concatenation — the old form
    materialized every batch leaf twice (``jnp.concatenate([x, x])``) just
    to reuse the flat M-axis vmap. Returns (losses, fresh, second) with the
    planes packed. Values are identical per row (vmap rows are
    independent); the dispatch/pass count is what halves."""
    stacked = jax.tree.map(
        lambda p, w: jnp.stack(
            [jnp.broadcast_to(p[None], (m,) + p.shape), w.astype(p.dtype)]),
        params, pts)
    losses2, grads2 = jax.vmap(vgrad_per, in_axes=(0, None))(stacked, batch)
    fresh = layout.pack_worker(jax.tree.map(lambda g: g[0], grads2))
    second = layout.pack_worker(jax.tree.map(lambda g: g[1], grads2))
    return losses2[0], fresh, second


def grouped_second_plane(layout: FlatLayout, ring, slot, batch, m: int,
                         vgrad) -> jnp.ndarray:
    """The grouped second evaluation: one broadcast-point ``vgrad`` per
    OCCUPIED ring row (a fixed-R masked ``lax.scan``), scattered into the
    (M, n_flat) second plane by each worker's slot. Every worker still
    sees its OWN sample ξ_m^k — only the evaluation point is shared — so
    the plane feeds ``kops.batched_diff_sq_norm`` without any re-gather.

    The weight traffic drops M× → R× (each occupied row fetches θ once for
    all its workers); the arithmetic INFLATES to occupancy × M row-evals,
    so this wins exactly when the eval is weight-bandwidth-bound (large n,
    small per-worker batch, R ≪ M — the federated LM regime) and loses
    when it is compute-bound (CPU logreg). Hence opt-in (``group_evals``).
    """
    rr = jax.tree.leaves(ring)[0].shape[0]

    def body(acc, r):
        def eval_row(a):
            with jax.named_scope("cada.rule_state"):
                row = jax.tree.map(lambda x: x[r], ring)
            _, g = vgrad(row, batch)
            return jnp.where((slot == r)[:, None], layout.pack_worker(g), a)

        return jax.lax.cond(jnp.any(slot == r), eval_row, lambda a: a,
                            acc), None

    acc0 = jnp.zeros((m, layout.n_flat), jnp.float32)
    plane, _ = jax.lax.scan(body, acc0, jnp.arange(rr))
    return plane


def eval_two_point(strategy, layout: FlatLayout, extras: dict, params,
                   batch, m: int, *, vgrad, vgrad_per=None,
                   fuse_evals: bool = False, group_evals: bool = False,
                   cohort=None):
    """The ONE home of the two-point eval dispatch, shared by
    :func:`flat_comm_round`, :func:`flat_cohort_round` and the async gate
    (sim/runtime.py). Returns ``(losses, fresh, second)`` packed planes
    (``second`` is None for single-eval rules).

    ``cohort`` ((C,) int32 global worker ids, or None): cohort-virtualized
    round. ``m`` is then C, ``batch`` holds only the cohort rows, and the
    indexed family's full-length (M,) slot vector is sliced to the cohort
    before the gather — rings and shared points stay server-resident at
    full M semantics while only C rows are ever evaluated.

    Dispatch order: the strategy's INDEXED family first
    (``second_eval_indexed`` — the stale-iterate ring). ``slot=None``
    degenerates to the shared broadcast point (CADA1's snapshot, exactly
    the old collapsed form). A real slot index picks one of three
    bit-compatible evaluation shapes:

      * default        — gather ``ring[slot]`` (R → M rows) and
        ``vgrad_per``: BIT-IDENTICAL to the old dense plane (same row
        values, same call);
      * ``fuse_evals`` — gather, then stack fresh+second into one vmapped
        call (:func:`stacked_two_point_eval`) — identical values, half the
        dispatches;
      * ``group_evals`` — NO gather: ≤R broadcast-point evals
        (:func:`grouped_second_plane`) — the M× → R× weight-traffic form
        (same math per worker; the broadcast-θ eval may differ from the
        per-row vmap by float ulps, so it is opt-in).

    The legacy dense ``second_eval_per_worker`` hook is honored last, for
    external strategies without a ring.

    On the device the evaluations are named ``cada.grad_eval``, the ring
    gather ``cada.rule_state`` and the packing ``cada.pack``. The fresh and
    second evaluations share one name: on the stacked route they are rows
    of one vmapped call, which no name can divide.
    """
    with jax.named_scope("cada.grad_eval"):
        indexed = strategy.second_eval_indexed(extras)
        if indexed is not None:
            ring, slot = indexed
            if cohort is not None and slot is not None:
                slot = slot[cohort]
            if slot is None:  # degenerate ring: one shared point
                shared_pt = jax.tree.map(lambda x: jnp.squeeze(x, 0), ring)
                losses, fresh_tree = vgrad(params, batch)
                _, second_tree = vgrad(shared_pt, batch)
                return (losses, layout.pack_worker(fresh_tree),
                        layout.pack_worker(second_tree))
            if group_evals:
                losses, fresh_tree = vgrad(params, batch)
                return (losses, layout.pack_worker(fresh_tree),
                        grouped_second_plane(layout, ring, slot, batch, m,
                                             vgrad))
            with jax.named_scope("cada.rule_state"):
                pts = jax.tree.map(lambda x: x[slot], ring)
            if fuse_evals:
                return stacked_two_point_eval(layout, params, pts, batch, m,
                                              vgrad_per)
            losses, fresh_tree = vgrad(params, batch)
            _, second_tree = vgrad_per(pts, batch)
            return (losses, layout.pack_worker(fresh_tree),
                    layout.pack_worker(second_tree))

        shared_pt = strategy.second_eval_shared(extras)
        perw_pts = strategy.second_eval_per_worker(extras)
        if perw_pts is not None and fuse_evals:
            return stacked_two_point_eval(layout, params, perw_pts, batch, m,
                                          vgrad_per)
        losses, fresh_tree = vgrad(params, batch)
        fresh = layout.pack_worker(fresh_tree)
        if shared_pt is not None:
            _, second_tree = vgrad(shared_pt, batch)
            second = layout.pack_worker(second_tree)
        elif perw_pts is not None:
            _, second_tree = vgrad_per(perw_pts, batch)
            second = layout.pack_worker(second_tree)
        else:
            second = None
        return losses, fresh, second


# ------------------------------------------------------------- shared round

def flat_comm_round(strategy, layout: FlatLayout, comm: FlatCommState,
                    params, params_flat, batch, k, *, vgrad,
                    vgrad_per: Callable | None = None,
                    fuse_evals: bool = True,
                    group_evals: bool = False,
                    interpret=None, shard=None,
                    participation=None,
                    local_steps=None) -> FlatCommRoundResult:
    """One communication round of Algorithm 1 (lines 4-15) on flat buffers.

    Semantically identical to ``comm.comm_round`` (the fused-vs-reference
    parity test pins this); the per-iteration cost is what changes:

      * rules with a second gradient evaluation (CADA1's snapshot, CADA2's
        stale-iterate ring) dispatch through :func:`eval_two_point`:
        ``fuse_evals`` stacks both evaluations onto one vmapped call via a
        broadcast 2-way eval axis (identical values — half the dispatches;
        set False when ``vgrad``/``vgrad_per`` are pod-manual shard_maps
        whose in-specs pin the M-leading axis), ``group_evals`` runs ≤R
        broadcast-point evaluations over the ring instead of gathering M
        rows (the M× → R× weight-traffic form — opt-in, see
        :func:`grouped_second_plane`);
      * the delta / mask-merge / eq. (3) aggregation are whole-plane ops;
      * the LHS norms ride the batched one-pass kernel (kernels/ops.py).

    ``shard`` (static, ``distributed.sharding.FlatSharding`` or None)
    threads the flat-plane sharding through the round: the LHS norms run
    shard-local with one psum, and the wire / eq. (3) aggregation are
    pinned to the worker-plane layout so GSPMD never reshards a full plane
    mid-round. A strategy may also ship a true SPARSE wire
    (``flat_sparse_wire`` returning (values, indices) pairs sized k): the
    pair is what crosses the simulated collective and is scattered back
    server-side — bit-equal to the dense masked plane.

    ``participation`` ((M,) bool or None) models PARTIAL PARTICIPATION
    (repro.sim's heterogeneous-cluster runtime): a non-participating worker
    never uploads this round — not even when its staleness is capped (it is
    offline, so the cap fires on its next participating round) — and its
    staleness keeps growing. ``None`` (the default) leaves the round's
    graph completely unchanged, which is what keeps the sim's degenerate
    zero-latency config bit-exact against the plain engine.

    ``local_steps`` belongs to the PAYLOAD/CADENCE axis and is only legal
    for delta-payload rules (``strategy.delta_payload`` — local_momentum /
    fedadam): those ship an accumulated local-optimizer model delta
    instead of one fresh gradient, the batch leads with the local-steps
    axis H (see :func:`batch_has_local_axis`), and ``local_steps``
    (None | scalar | (M,)) is how many of the H padded steps each worker
    runs this round. For the 8 gradient-payload rules the kwarg must stay
    None and the round's graph is byte-identical to the pre-axis form.
    """
    r = strategy.rule
    m = comm.staleness.shape[0]
    if local_steps is not None and not strategy.delta_payload:
        raise ValueError(
            f"rule kind {r.kind!r} ships per-iteration gradients; "
            "local_steps is only meaningful for delta-payload rules "
            "(local_momentum, fedadam)")

    # Line 4 (rule-owned): e.g. CADA1 snapshot refresh every D iterations.
    with jax.named_scope("cada.rule_state"):
        extras = strategy.flat_pre_step(comm.extras, params, params_flat, k)

    if strategy.delta_payload:
        # Payload/cadence branch: the worker runs h_w local optimizer
        # steps and ships the accumulated model delta θ^k − θ_m^(h) (fp32)
        # in place of the fresh gradient. Substituting that payload for
        # ``fresh`` leaves the rest of the round untouched: with the
        # always-upload cadence below, worker_grads telescopes to the last
        # shipped payload, so ∇̄ ≡ mean_m(payload) exactly and the rule's
        # server optimizer (sgd(1.0) / server Adam) turns eq. (3) into
        # periodic averaging / FedAdam.
        batch_h = (batch if batch_has_local_axis(r, local_steps)
                   else jax.tree.map(lambda x: x[None], batch))
        h_steps = local_steps_vector(r, m, batch_h, local_steps)
        with jax.named_scope("cada.grad_eval"):
            losses, fresh, cache = strategy.flat_local_payload(
                layout, extras, params, params_flat, batch_h, m, vgrad_per,
                h_steps)
        second = None
        ctx = FlatCommContext(layout=layout, params=params,
                              params_flat=params_flat, batch=batch,
                              fresh=fresh, second=second,
                              comm=comm._replace(extras=extras),
                              step=k, m=m, interpret=interpret, shard=shard,
                              participation=participation)
        # always-upload cadence: the "skip" axis is folded into h_w
        lhs = jnp.full((m,), jnp.inf, jnp.float32)
    else:
        h_steps = None
        # Lines 6/8: fresh gradients at θ^k, plus the rule's second
        # evaluation (ring-indexed / shared / legacy dense — see
        # eval_two_point).
        losses, fresh, second = eval_two_point(
            strategy, layout, extras, params, batch, m, vgrad=vgrad,
            vgrad_per=vgrad_per, fuse_evals=fuse_evals,
            group_evals=group_evals)

        ctx = FlatCommContext(layout=layout, params=params,
                              params_flat=params_flat, batch=batch,
                              fresh=fresh, second=second,
                              comm=comm._replace(extras=extras),
                              step=k, m=m, interpret=interpret, shard=shard,
                              participation=participation)

        # Lines 7/9: rule LHS vs the shared recent-progress RHS.
        with jax.named_scope("cada.gate"):
            lhs, cache = strategy.flat_lhs(ctx, extras)
    with jax.named_scope("cada.gate"):
        rhs = r.rhs(comm.diff_hist)
        # Line 10: upload if the condition is VIOLATED or staleness capped.
        upload = (lhs > rhs) | (comm.staleness >= r.max_delay)
        if participation is not None:
            upload = upload & participation

    with jax.named_scope("cada.eq3"):
        # Eq. (3): innovation delta, wire format, masked aggregation — each
        # a single whole-plane op (one (M, n_flat) sweep instead of ~6
        # tree_maps).
        wg32 = comm.worker_grads.astype(jnp.float32)
        delta = strategy.flat_wire_delta(ctx, extras, cache, fresh - wg32)
        sparse = strategy.flat_sparse_wire(ctx, extras, cache, delta)
        if sparse is not None:
            # True sparse wire: the (M, K) value/index pair is the
            # collective payload; the dense plane is reconstructed
            # server-side. Values are masked and cast exactly like the dense
            # wire, so the two paths are bit-equal wherever the extraction
            # captured the full support.
            vals, idx = sparse
            vals = jnp.where(upload[:, None], vals, 0.0).astype(
                comm.worker_grads.dtype)
            wire = sparse_rows_to_dense(idx, vals, layout.n_flat)
        else:
            wire = jnp.where(upload[:, None], delta, 0.0).astype(
                comm.worker_grads.dtype)
        if shard is not None:
            # pin the wire to the worker-plane layout: the cross-worker
            # mean below IS the gated collective, and an unpinned
            # intermediate lets GSPMD gather the full plane before reducing
            # it.
            wire = shard.constrain_worker(wire)
        # Order-fixed row accumulation (kops.eq3_row_mean, one pass over
        # the wire plane into ∇̄): masked zero rows are exact no-ops, so
        # this dense masked mean is BIT-IDENTICAL to the cohort plane's
        # C-row sum below (flat_cohort_round) — the parity the cohort
        # tests pin.
        nabla = kops.eq3_row_mean(wire, m, comm.nabla.astype(jnp.float32),
                                  shard=shard, interpret=interpret
                                  ).astype(comm.nabla.dtype)
        if shard is not None:
            nabla = shard.constrain_server(nabla)
        worker_grads = (wg32 + wire.astype(jnp.float32)
                        ).astype(comm.worker_grads.dtype)

        staleness = jnp.where(upload, 1, comm.staleness + 1)
    with jax.named_scope("cada.rule_state"):
        extras = strategy.flat_post_upload(extras, cache, upload, ctx)

    uploads = jnp.sum(upload.astype(jnp.int32))
    # offline workers evaluate nothing — charge grad evals to participants
    n_active = (jnp.asarray(m, jnp.int32) if participation is None
                else jnp.sum(participation.astype(jnp.int32)))
    if strategy.delta_payload:
        # one eval per LOCAL step: Σ_active h_w
        grad_evals = jnp.sum(h_steps if participation is None
                             else jnp.where(participation, h_steps, 0))
    else:
        grad_evals = n_active * strategy.grad_evals_per_iter
    metrics = {
        "uploads": uploads,
        # fraction of ACTIVE workers that skipped (an offline worker does
        # not "skip" — it was never asked)
        "skip_rate": 1.0 - uploads.astype(jnp.float32) / n_active,
        "upload_mask": upload,
        "staleness": staleness,
        "rhs": rhs,
        # full per-worker gate LHS (inf for threshold-free rules) — the
        # obs.metrics.CommLedger derives LHS−RHS gate margins from this
        "lhs": lhs,
        "mean_lhs": jnp.mean(jnp.where(jnp.isfinite(lhs), lhs, 0.0)),
        "max_staleness": jnp.max(staleness),
        "grad_evals": grad_evals,
        "bytes_up": (uploads.astype(jnp.float32)
                     * strategy.bytes_per_upload(layout.n)),
    }
    new_comm = FlatCommState(nabla=nabla, worker_grads=worker_grads,
                             staleness=staleness, diff_hist=comm.diff_hist,
                             extras=extras)
    return FlatCommRoundResult(losses=losses, comm=new_comm, upload=upload,
                               metrics=metrics)


# ------------------------------------------------------- cohort-virtualized
#
# At federated scale (M ≥ 10⁴) the dense (M, n_flat) worker planes stop
# fitting on device — and eq. (3) only ever needs the AGGREGATE of the
# uploaded innovations, while each worker's stale-gradient row is touched
# exactly on the rounds that worker is sampled. The cohort plane exploits
# that: per round only the C sampled workers' rows exist on device,
# gathered from a host-resident numpy pool and scattered back after the
# round, while the server keeps only the (n_flat,) aggregate, the (M,)
# staleness/slot/period vectors, the RHS ring and shared extras (CADA1's
# snapshot, CADA2's stale-iterate ring). Device worker-plane bytes and
# per-round eval compute are O(C·n); the O(M·n) planes live on host.
#
# Semantics: a cohort round is EXACTLY the dense plane run with
# ``participation`` = the cohort's indicator mask — offline workers age
# (+1 staleness), upload nothing, keep their rows and periods, and keep
# their ring slots referenced. The order-fixed eq. (3) accumulation
# (kops.eq3_row_mean) makes the parity BIT-exact in fp32, masked dense
# mean vs C-row cohort sum; tests/test_cohort_plane.py pins it for all
# registered rules.


class WorkerPool:
    """Host-resident per-worker state pool backing the cohort plane.

    Numpy-backed (M, n_flat) planes — ``worker_grads`` plus whatever
    per-worker planes the strategy pools (``strategy.pooled_extras()``:
    CADA1's ``worker_delta``, laq/topk's error-feedback ``residual``).
    ``gather`` streams the C sampled rows onto device (ascending worker
    order — the order the parity depends on); ``scatter`` writes the
    round's updated rows back. Planes keep their storage dtype (bf16
    planes round-trip bit-exactly via ml_dtypes' numpy bfloat16).

    Transfers are FUSED: the P planes' cohort rows are staged into one
    preallocated (P, C, n_flat) host buffer, so a round costs a single
    H2D dispatch (``gather_fused``) and a single D2H copy
    (``scatter_fused``) instead of one per plane. The staging buffer is
    double-slotted so the pipelined driver can stage round i+1's rows
    while round i's H2D transfer may still be draining. The dict-valued
    ``gather``/``scatter`` route through the same staging path.

    ``storage="memmap"`` backs each plane with an ``np.memmap`` file
    under ``path`` so M beyond RAM works: only the touched pages are
    resident, gathers/scatters fault in exactly the cohort's rows, and
    checkpoint ``state_dict``/``load_state_dict`` round-trip in place
    through the mapping. ``nbytes`` stays the logical O(M·n) plane total
    (resident for RAM pools, address-space mapped for memmap pools);
    ``mapped_nbytes``/``resident_nbytes`` report the split.
    """

    STORAGES = ("ram", "memmap")

    def __init__(self, planes: dict, storage: str = "ram",
                 path: str | None = None):
        if storage not in self.STORAGES:
            raise ValueError(f"storage must be one of {self.STORAGES}, "
                             f"got {storage!r}")
        if storage == "memmap" and path is None:
            raise ValueError('storage="memmap" needs path= (a directory '
                             "for the plane files)")
        self.storage = storage
        self.path = path
        if storage == "memmap":
            os.makedirs(path, exist_ok=True)
            owned = {}
            for name, v in planes.items():
                src = np.asarray(v)
                mm = np.memmap(os.path.join(path, f"{name}.plane"),
                               dtype=src.dtype, mode="w+", shape=src.shape)
                mm[...] = src
                owned[name] = mm
            self.planes = owned
        else:
            # own the storage: np views of jax arrays arrive read-only,
            # and scatter writes in place
            self.planes = {name: (v if isinstance(v, np.ndarray)
                                  and v.flags.writeable else np.array(v))
                           for name, v in planes.items()}
        shapes = {v.shape for v in self.planes.values()}
        if len(shapes) != 1:
            raise ValueError(f"pool planes disagree on shape: {shapes}")
        self._order = tuple(self.planes)
        dtypes = {v.dtype for v in self.planes.values()}
        self._dtype = dtypes.pop() if len(dtypes) == 1 else None
        self._stage = None        # (2, P, C, n_flat) host staging buffer

    @property
    def m(self) -> int:
        return next(iter(self.planes.values())).shape[0]

    @property
    def n_flat(self) -> int:
        return next(iter(self.planes.values())).shape[1]

    @property
    def plane_order(self) -> tuple:
        """Fixed plane stacking order of the fused (P, C, n_flat) block."""
        return self._order

    @property
    def plane_dtype(self):
        """The planes' common storage dtype (None if they disagree —
        which disables the fused staging path)."""
        return self._dtype

    @property
    def nbytes(self) -> int:
        """Logical plane bytes (the O(M·n) side of the split) — host RAM
        for ``storage="ram"``, mapped address space for memmap pools."""
        return int(sum(v.nbytes for v in self.planes.values()))

    @property
    def mapped_nbytes(self) -> int:
        """Bytes living in memmap files rather than RAM."""
        if self.storage != "memmap":
            return 0
        return int(sum(v.nbytes for v in self.planes.values()))

    @property
    def resident_nbytes(self) -> int:
        """Bytes guaranteed RAM-resident: RAM planes + staging buffers.
        (Memmap planes additionally cache touched pages at the OS's
        discretion — that part is reclaimable and not counted.)"""
        planes = 0 if self.storage == "memmap" else self.nbytes
        stage = self._stage.nbytes if self._stage is not None else 0
        return int(planes + stage)

    def device_row_bytes(self, c: int) -> int:
        """Device bytes a C-row gather materializes (the O(C·n) side)."""
        return int(sum(v.dtype.itemsize * c * v.shape[1]
                       for v in self.planes.values()))

    # ---- fused staging path (one host copy per round per direction)
    def _stage_view(self, c: int, slot: int) -> np.ndarray:
        if self._stage is None or self._stage.shape[2] != c:
            self._stage = np.empty(
                (2, len(self._order), c, self.n_flat), self._dtype)
        return self._stage[slot & 1]

    def gather_fused(self, cohort, slot: int = 0) -> jnp.ndarray:
        """Cohort rows -> device as ONE (P, C, n_flat) block.

        All planes' rows are staged into the reused host buffer (slot
        ``slot & 1`` of the double buffer), then shipped in a single H2D
        dispatch. Plane p is ``plane_order[p]``; rows follow ``cohort``
        order (sorted ascending — the order the parity depends on).
        """
        if self._dtype is None:
            raise ValueError("fused gather needs a uniform plane dtype; "
                             f"pool has {[str(v.dtype) for v in self.planes.values()]}")
        idx = np.asarray(cohort, dtype=np.intp)
        buf = self._stage_view(idx.shape[0], slot)
        for p, name in enumerate(self._order):
            np.take(self.planes[name], idx, axis=0, out=buf[p])
        # jnp.array COPIES out of the staging buffer (jnp.asarray may
        # alias host memory on CPU — the buffer is reused next round)
        return jnp.array(buf)

    def scatter_fused(self, cohort, fused) -> None:
        """Write a (P, C, n_flat) fused block back into the planes.

        ``np.asarray(fused)`` is the round's single D2H copy (it blocks
        until the producing step is done — the pipelined driver calls
        this one round late so the wait rides under the next round's
        compute)."""
        idx = np.asarray(cohort, dtype=np.intp)
        arr = np.asarray(fused)
        for p, name in enumerate(self._order):
            plane = self.planes[name]
            rows = arr[p]
            if rows.dtype != plane.dtype:
                rows = rows.astype(plane.dtype)
            plane[idx] = rows

    def gather(self, cohort) -> dict:
        """Cohort rows -> device: {name: (C, n_flat) jnp array}.

        Routed through the fused staging buffer — one H2D for all
        planes; the per-name values are device views into the block."""
        if self._dtype is None:        # mixed dtypes: per-plane fallback
            idx = np.asarray(cohort)
            return {name: jnp.asarray(plane[idx])
                    for name, plane in self.planes.items()}
        fused = self.gather_fused(cohort)
        return {name: fused[p] for p, name in enumerate(self._order)}

    def scatter(self, cohort, rows: dict) -> None:
        """Write the round's updated (C, n_flat) rows back into the pool
        (one fused D2H copy when the rows are device-resident)."""
        if self._dtype is None:
            idx = np.asarray(cohort)
            for name, vals in rows.items():
                plane = self.planes[name]
                plane[idx] = np.asarray(vals).astype(plane.dtype,
                                                     copy=False)
            return
        vals = [rows[name] for name in self._order]
        if all(isinstance(v, jax.Array) for v in vals):
            fused = jnp.stack([v.astype(self._dtype) for v in vals])
        else:
            fused = np.stack([np.asarray(v).astype(self._dtype,
                                                   copy=False)
                              for v in vals])
        self.scatter_fused(cohort, fused)

    def flush(self) -> None:
        """Sync memmap-backed planes to their files (no-op for RAM)."""
        if self.storage == "memmap":
            for v in self.planes.values():
                v.flush()

    def resum_nabla(self) -> np.ndarray:
        """Drift guard: recompute ∇̄ = mean_m(worker_grads) from the pool.

        The incremental aggregate satisfies ∇̄ ≡ mean(worker_grads)
        exactly in real arithmetic; in fp32 each round adds rounding noise.
        This host-side re-sum (fp64 accumulate, fp32 result) restores the
        invariant — config-off by default (``resum_every`` on the engine),
        cheap (one host pass over the pool, no device traffic).
        """
        wg = self.planes["worker_grads"].astype(np.float64)
        return (wg.sum(axis=0) / wg.shape[0]).astype(np.float32)

    # ---- checkpoint (the planes ride checkpoint.io as ordinary leaves;
    # (M, n_flat) planes reshard through ``_reshard_flat`` like any other
    # flat worker plane)
    def state_dict(self) -> dict:
        return dict(self.planes)

    def load_state_dict(self, d: dict) -> None:
        for name in self.planes:
            arr = np.asarray(d[name])
            if arr.shape != self.planes[name].shape:
                raise ValueError(
                    f"pool plane {name!r}: shape {arr.shape} != "
                    f"{self.planes[name].shape}")
            # in place: memmap planes stay mapped, RAM planes stay owned
            self.planes[name][...] = arr.astype(self.planes[name].dtype,
                                                copy=False)


class CohortServerState(NamedTuple):
    """Device-resident server state under the cohort plane: everything
    that is NOT an O(M·n) per-worker plane. ``extras`` holds the shared /
    indexed strategy extras (snapshot, ring, (M,) slot/period vectors);
    the pooled planes live in the :class:`WorkerPool`.
    ``record_progress`` works on this state unchanged."""
    nabla: jnp.ndarray        # (n_flat,) storage dtype
    staleness: jnp.ndarray    # (M,) int32
    diff_hist: jnp.ndarray    # (d_max,) fp32 RHS ring buffer
    extras: dict              # non-pooled strategy extras


class FlatCohortRoundResult(NamedTuple):
    losses: jnp.ndarray       # (C,)
    server: CohortServerState  # diff_hist NOT yet updated (record_progress)
    rows: dict                # updated pooled rows -> WorkerPool.scatter
    upload: jnp.ndarray       # (C,) bool
    metrics: dict


def init_cohort_state(strategy, layout: FlatLayout, params, m: int,
                      grad_dtype=jnp.float32, params_flat=None,
                      pool_storage: str = "ram",
                      pool_path: str | None = None):
    """Fresh cohort-plane state: (CohortServerState, WorkerPool).

    Field-for-field the split of :func:`init_flat_comm_state`'s state:
    pooled per-worker planes land in the numpy pool (``pool_storage`` /
    ``pool_path`` pick RAM vs memmap backing), everything else on
    device. τ_m starts at D so every worker force-uploads on its first
    sampled round. Plane order is ``worker_grads`` first, then the
    strategy's ``pooled_extras()`` order — the fused staging block's
    stacking order.
    """
    r = strategy.rule
    if params_flat is None:
        params_flat = layout.pack(params)
    full_extras = strategy.init_flat_extras(layout, params, params_flat, m,
                                            grad_dtype)
    pooled = strategy.pooled_extras()
    planes = {"worker_grads": np.zeros((m, layout.n_flat),
                                       np.dtype(grad_dtype))}
    for name in pooled:
        if name in full_extras:
            planes[name] = np.asarray(full_extras[name])
    server_extras = {name: val for name, val in full_extras.items()
                     if name not in planes}
    server = CohortServerState(
        nabla=jnp.zeros((layout.n_flat,), grad_dtype),
        staleness=jnp.full((m,), r.max_delay, jnp.int32),
        diff_hist=jnp.zeros((r.d_max,), jnp.float32),
        extras=server_extras)
    return server, WorkerPool(planes, storage=pool_storage, path=pool_path)


def flat_cohort_round(strategy, layout: FlatLayout,
                      server: CohortServerState, rows: dict, params,
                      params_flat, batch, k, cohort, *, m_total: int,
                      vgrad, vgrad_per: Callable | None = None,
                      fuse_evals: bool = True,
                      interpret=None) -> FlatCohortRoundResult:
    """One Algorithm-1 round on the cohort-virtualized plane.

    ``rows`` is the WorkerPool gather for ``cohort`` ((C,) int32 SORTED
    ascending global worker ids); ``batch`` holds only the cohort rows
    ((C, b, ...) leaves). Bit-exact against :func:`flat_comm_round` run
    with ``participation`` = the cohort indicator on the dense plane:

      * per-row quantities (grads, LHS norms, wires) never mix rows, so
        the C evaluated rows carry the dense run's exact bits;
      * the eq. (3) aggregate is the order-fixed C-row sum / m_total —
        bit-identical to the dense masked mean (see ``kops.eq3_row_mean``),
        with NO full-plane re-sum anywhere;
      * offline workers age exactly like dense non-participants: staleness
        +1, rows/periods untouched, ring slots still refcounted (the
        cohort-aware strategy hooks handle the (M,)-resident extras).
    """
    r = strategy.rule
    c = rows["worker_grads"].shape[0]
    pooled = strategy.pooled_extras()
    merged = {**server.extras, **{name: rows[name] for name in pooled}}
    stale_c = server.staleness[cohort]
    comm_row = FlatCommState(
        nabla=server.nabla, worker_grads=rows["worker_grads"],
        staleness=stale_c, diff_hist=server.diff_hist, extras=merged)

    with jax.named_scope("cada.rule_state"):
        extras = strategy.flat_pre_step(merged, params, params_flat, k)
    if strategy.delta_payload:
        # Payload/cadence branch on the cohort plane: the C sampled
        # workers run their local steps (fixed H — the cohort plane does
        # not carry the sim's adaptive schedule) and ship model deltas;
        # see flat_comm_round. ``batch`` is (H, C, b, ...) when H > 1.
        batch_h = (batch if batch_has_local_axis(r, None)
                   else jax.tree.map(lambda x: x[None], batch))
        h_steps = local_steps_vector(r, c, batch_h, None)
        with jax.named_scope("cada.grad_eval"):
            losses, fresh, cache = strategy.flat_local_payload(
                layout, extras, params, params_flat, batch_h, c, vgrad_per,
                h_steps)
        second = None
        ctx = FlatCommContext(layout=layout, params=params,
                              params_flat=params_flat, batch=batch,
                              fresh=fresh, second=second,
                              comm=comm_row._replace(extras=extras),
                              step=k, m=c, interpret=interpret, shard=None,
                              participation=None, cohort=cohort)
        lhs = jnp.full((c,), jnp.inf, jnp.float32)
    else:
        h_steps = None
        losses, fresh, second = eval_two_point(
            strategy, layout, extras, params, batch, c, vgrad=vgrad,
            vgrad_per=vgrad_per, fuse_evals=fuse_evals, cohort=cohort)

        ctx = FlatCommContext(layout=layout, params=params,
                              params_flat=params_flat, batch=batch,
                              fresh=fresh, second=second,
                              comm=comm_row._replace(extras=extras),
                              step=k, m=c, interpret=interpret, shard=None,
                              participation=None, cohort=cohort)

        with jax.named_scope("cada.gate"):
            lhs, cache = strategy.flat_lhs(ctx, extras)
    with jax.named_scope("cada.gate"):
        rhs = r.rhs(server.diff_hist)
        upload = (lhs > rhs) | (stale_c >= r.max_delay)

    with jax.named_scope("cada.eq3"):
        wg32 = rows["worker_grads"].astype(jnp.float32)
        delta = strategy.flat_wire_delta(ctx, extras, cache, fresh - wg32)
        sparse = strategy.flat_sparse_wire(ctx, extras, cache, delta)
        if sparse is not None:
            vals, idx = sparse
            vals = jnp.where(upload[:, None], vals, 0.0).astype(
                rows["worker_grads"].dtype)
            wire = sparse_rows_to_dense(idx, vals, layout.n_flat)
        else:
            wire = jnp.where(upload[:, None], delta, 0.0).astype(
                rows["worker_grads"].dtype)
        # ∇̄ += Σ_cohort δ_m / M — the incremental aggregate; the (M-C)
        # offline rows would contribute exact zeros, so the dense masked
        # mean is reproduced bit-for-bit without ever materializing it.
        nabla = kops.eq3_row_mean(wire, m_total,
                                  server.nabla.astype(jnp.float32),
                                  interpret=interpret
                                  ).astype(server.nabla.dtype)
        worker_grads = (wg32 + wire.astype(jnp.float32)
                        ).astype(rows["worker_grads"].dtype)

        staleness = (server.staleness + 1).at[cohort].set(
            jnp.where(upload, 1, stale_c + 1))
    with jax.named_scope("cada.rule_state"):
        extras = strategy.flat_post_upload(extras, cache, upload, ctx)
    new_rows = {"worker_grads": worker_grads,
                **{name: extras[name] for name in pooled}}
    server_extras = {name: v for name, v in extras.items()
                     if name not in pooled}

    uploads = jnp.sum(upload.astype(jnp.int32))
    metrics = {
        "uploads": uploads,
        "skip_rate": 1.0 - uploads.astype(jnp.float32) / c,
        "upload_mask": upload,
        "staleness": staleness[cohort],
        "rhs": rhs,
        # per-cohort-member gate LHS for the obs ledger's margin split
        "lhs": lhs,
        "mean_lhs": jnp.mean(jnp.where(jnp.isfinite(lhs), lhs, 0.0)),
        "max_staleness": jnp.max(staleness),
        "grad_evals": (jnp.sum(h_steps) if strategy.delta_payload
                       else jnp.asarray(c, jnp.int32)
                       * strategy.grad_evals_per_iter),
        "bytes_up": (uploads.astype(jnp.float32)
                     * strategy.bytes_per_upload(layout.n)),
    }
    new_server = CohortServerState(nabla=nabla, staleness=staleness,
                                   diff_hist=server.diff_hist,
                                   extras=server_extras)
    return FlatCohortRoundResult(losses=losses, server=new_server,
                                 rows=new_rows, upload=upload,
                                 metrics=metrics)


def record_progress(comm: FlatCommState, dtheta_sq, k) -> FlatCommState:
    """Push ||θ^{k+1} − θ^k||² into the RHS ring buffer (line 17's tail)."""
    d_max = comm.diff_hist.shape[0]
    diff_hist = jax.lax.dynamic_update_index_in_dim(
        comm.diff_hist, dtheta_sq.astype(jnp.float32), k % d_max, axis=0)
    return comm._replace(diff_hist=diff_hist)


def nabla_f32(comm: FlatCommState) -> jnp.ndarray:
    """The server-update driver ∇^k as an fp32 flat buffer (line 16)."""
    return comm.nabla.astype(jnp.float32)


# ------------------------------------------------- pipelined cohort driver
#
# The serial cohort loop is a chain per round: host gather (H2D), jitted
# step, host scatter whose np.asarray BLOCKS on the D2H transfer. XLA
# dispatch is asynchronous, so the chain wastes the device: while the
# host waits on round i's transfers the device is idle, and vice versa.
#
# The pipelined driver reorders TRANSFERS, never arithmetic:
#
#   round i:   enqueue step(i)            [device busy with round i]
#              scatter out(i-1)           [D2H wait rides under step(i)]
#              stage + dispatch rows(i+1) [H2D rides under step(i)]
#
# Deferring round i's scatter one round means the pool misses round i's
# updates when round i+1's rows are staged. When consecutive cohorts
# overlap, the overlapping rows are instead forwarded ON DEVICE: the
# precomputed ``src`` schedule maps each round-(i+1) cohort position to
# its position in round i's output block (or -1), and
# :func:`patch_fused_rows` substitutes round i's exact output rows. The
# substituted values are bit-identical to what the scatter+gather round
# trip would have produced, so the pipeline is bit-exact to the serial
# loop — pinned for every registered rule by tests/test_cohort_pipeline.


def cohort_overlap_schedule(cohorts: np.ndarray) -> np.ndarray:
    """(T, C) int32 forwarding schedule for the deferred-scatter pipeline.

    ``src[i, j]`` = position of worker ``cohorts[i, j]`` inside
    ``cohorts[i-1]`` (whose output block is still on device when round i
    runs), or -1 when the worker was not in the previous cohort. Row 0 is
    all -1. Rows must be sorted ascending (``sample_cohorts`` invariant).
    """
    cohorts = np.asarray(cohorts, np.int64)
    t, c = cohorts.shape
    src = np.full((t, c), -1, np.int32)
    for i in range(1, t):
        prev = cohorts[i - 1]
        pos = np.searchsorted(prev, cohorts[i])
        pos = np.clip(pos, 0, c - 1)
        hit = prev[pos] == cohorts[i]
        src[i] = np.where(hit, pos, -1).astype(np.int32)
    return src


def patch_fused_rows(fused: jnp.ndarray, prev: jnp.ndarray,
                     src: jnp.ndarray) -> jnp.ndarray:
    """Forward the previous round's output rows into this round's gather.

    ``fused``/``prev`` are (P, C, n_flat) / (P, C_prev, n_flat) blocks,
    ``src`` the (C,) schedule row from :func:`cohort_overlap_schedule`.
    Positions with ``src < 0`` keep the gathered rows. All shapes are
    static, so the patch compiles once per (C, C_prev).

    Bit-exactness contract: the pipelined driver runs this as its OWN
    jitted call (:func:`_patch_fused_jit`) and feeds the materialized
    result to the cohort step. Inlining the select into the step is NOT
    safe — XLA duplicates fused consumer chains under the select's two
    branches and LLVM contracts fma differently per copy, so a row
    arriving through the ``prev`` gather picks up different low bits
    than the SAME values arriving through ``fused``. Materializing the
    patch as an executable boundary makes the step consume one memory
    operand on both paths, which pins serial/pipelined parity by plain
    determinism."""
    safe = jnp.clip(src, 0, prev.shape[1] - 1)
    forwarded = prev[:, safe, :]
    return jnp.where((src >= 0)[None, :, None], forwarded, fused)


# the gathered block is staging output and never reused: donate it so the
# patch can write in place; ``prev`` is re-read by the deferred scatter
# and MUST NOT be donated.
_patch_fused_jit = jax.jit(patch_fused_rows, donate_argnums=(0,))


def split_fused_rows(fused: jnp.ndarray, order: tuple) -> dict:
    """(P, C, n_flat) block -> {plane_name: (C, n_flat)} views."""
    return {name: fused[p] for p, name in enumerate(order)}


def stack_fused_rows(rows: dict, order: tuple, dtype) -> jnp.ndarray:
    """{plane_name: (C, n_flat)} -> one (P, C, n_flat) block in the
    pool's storage dtype (the cast the host scatter used to do)."""
    return jnp.stack([rows[name].astype(dtype) for name in order])


def run_cohort_rounds(step_fn, state, pool: WorkerPool, batch_fn,
                      cohorts: np.ndarray, *, pipeline: bool = True,
                      metrics_every: int = 8, on_round=None,
                      on_round_every: int = 0,
                      trace=None, metrics_out: list | None = None):
    """Drive T cohort rounds through a fused jitted step.

    ``step_fn(state, fused, batch, cohort) -> (state, fused_out,
    metrics)`` may donate (state, fused) — serial and pipelined drive
    the SAME executable. ``batch_fn(i, cohorts[i])`` supplies round i's
    cohort batch; ``cohorts`` is (T, C) int32, every row sorted
    ascending with unique ids (validated up front — raises ValueError
    otherwise). An empty schedule returns ``(state, [])``.

    ``pipeline=False`` is the serial parity oracle: eager
    gather → step → scatter per round.

    ``pipeline=True`` double-buffers: round i+1's rows are staged and
    dispatched H2D while round i's step runs, and round i's scatter is
    deferred one round so its D2H wait rides under round i+1's compute.
    Rows that round i+1 shares with round i are stale in that early
    gather; they are forwarded from round i's device output by
    :func:`_patch_fused_jit` — a SEPARATE jitted call, so the step
    consumes one materialized block on both paths and parity with the
    serial oracle is plain single-executable determinism (see
    :func:`patch_fused_rows` for why inlining the select would break
    bit-exactness). Rounds with no overlap skip the patch entirely. The
    pending scatter is drained on ANY exit (including exceptions), so
    an interrupted run leaves the pool consistent through the last
    completed round.

    Metrics are accumulated device-side and fetched with one
    ``jax.device_get`` every ``metrics_every`` rounds (the losses trace
    rides in the same dicts); the partial device-side window is flushed
    on ANY exit too, so a traced/errored run never silently drops the
    tail ``< metrics_every`` rounds — pass ``metrics_out`` (a list; it
    doubles as the return value) to observe metrics through the last
    completed round even when the run raises. ``on_round(i, state) ->
    state|None`` fires every ``on_round_every`` rounds AFTER the pool is
    drained through round i (the ``resum_every`` drift-guard hook).
    ``trace`` is an ``obs.trace.Tracer`` (or None): each round emits
    gather/patch/step/scatter spans on the ``"pipeline"`` track — the
    one home for per-round phase timing; the bench harness reads
    ``trace.aggregate("pipeline")`` instead of keeping its own clocks.
    Returns (state, list-of-host-metric-dicts).
    """
    from ..obs.trace import as_tracer

    cohorts = np.asarray(cohorts, np.int32)
    t_rounds = cohorts.shape[0]
    mets_host: list = metrics_out if metrics_out is not None else []
    if t_rounds == 0:
        return state, mets_host
    # both drivers depend on sorted-unique rows (sample_cohorts already
    # guarantees it): the overlap schedule searchsorts the previous row,
    # so an unsorted cohort would silently forward the WRONG rows —
    # validate once up front instead of re-sorting per round, since
    # sorting here would desynchronize cohorts from batch_fn's batches
    if not (np.diff(cohorts, axis=1) > 0).all():
        raise ValueError(
            "run_cohort_rounds: every cohorts row must be sorted "
            "ascending with unique worker ids (the sample_cohorts "
            "invariant) — sort each cohort AND its batch together "
            "before calling")
    metrics_every = max(1, int(metrics_every))
    tracer = as_tracer(trace)

    mets_dev: list = []

    def flush_metrics():
        if mets_dev:
            mets_host.extend(jax.device_get(mets_dev))
            mets_dev.clear()

    # per-round cohort/src rows ride into the jitted calls as numpy args
    # (one inline transfer) — slicing a staged device matrix per round
    # costs a full op dispatch, ~4x the price of the whole patch call

    if not pipeline:
        # serial oracle: eager gather → step → scatter, same executable
        # as the pipelined path
        try:
            for i in range(t_rounds):
                with tracer.span("gather", track="pipeline"):
                    fused = pool.gather_fused(cohorts[i])
                with tracer.span("step", track="pipeline"):
                    state, out, met = step_fn(state, fused,
                                              batch_fn(i, cohorts[i]),
                                              cohorts[i])
                with tracer.span("scatter", track="pipeline"):
                    pool.scatter_fused(cohorts[i], out)
                mets_dev.append(met)
                if len(mets_dev) >= metrics_every:
                    flush_metrics()
                if on_round is not None and on_round_every \
                        and (i + 1) % on_round_every == 0:
                    state = _maybe(on_round(i, state), state)
        finally:
            flush_metrics()
        return state, mets_host

    src_sched = cohort_overlap_schedule(cohorts)
    has_overlap = (src_sched >= 0).any(axis=1)       # host-side, per round
    prev = None                        # round i-1's device output block
    with tracer.span("gather", track="pipeline"):
        fused_next = pool.gather_fused(cohorts[0], slot=0)
    pending = None                     # (cohort_np, device_out) to scatter
    try:
        for i in range(t_rounds):
            batch = batch_fn(i, cohorts[i])
            if has_overlap[i]:
                # rows shared with round i-1 are stale in the early
                # gather: forward them from prev in a separate jit call
                with tracer.span("patch", track="pipeline"):
                    fused_next = _patch_fused_jit(fused_next, prev,
                                                  src_sched[i])
            with tracer.span("step", track="pipeline"):
                state, out, met = step_fn(state, fused_next,
                                          batch, cohorts[i])
            with tracer.span("scatter", track="pipeline"):
                # round i-1's writeback: its D2H wait rides under step i
                if pending is not None:
                    pool.scatter_fused(*pending)
            pending = (cohorts[i], out)
            prev = out
            # stage round i+1 while step i runs; round i's rows are
            # forwarded on device by the src schedule, everything older
            # is already in the pool
            if i + 1 < t_rounds:
                with tracer.span("gather", track="pipeline"):
                    fused_next = pool.gather_fused(cohorts[i + 1],
                                                   slot=(i + 1) & 1)
            mets_dev.append(met)
            if len(mets_dev) >= metrics_every:
                flush_metrics()
            if on_round is not None and on_round_every \
                    and (i + 1) % on_round_every == 0:
                # the hook reads the pool: drain round i's rows first
                pool.scatter_fused(*pending)
                pending = None
                state = _maybe(on_round(i, state), state)
    finally:
        # drain on ANY exit: the pool is consistent — and the partial
        # metrics window fetched — through the last completed round even
        # when the run is interrupted mid-flight
        if pending is not None:
            pool.scatter_fused(*pending)
        flush_metrics()
    return state, mets_host


def _maybe(new_state, state):
    return state if new_state is None else new_state
