"""Hierarchical CADA: the paper's server/worker protocol mapped onto TPU
pods (DESIGN.md §3).

The paper's "worker" becomes the unit that actually pays for communication:
  * multi-pod mesh (pod, data, model): worker = pod (M = n_pods). Within a
    pod gradients average over cheap ICI; ACROSS pods the all-reduce of the
    masked innovations (eq. 3) is what CADA gates — skipped rounds eliminate
    the DCN transfer of a full fp32 gradient.
  * single-pod mesh (data, model): worker = data-parallel group (M = 16),
    matching the paper's M ≈ 10-20; the gated collective rides ICI.

This module keeps ONLY the pod concerns: sharding specs, microbatch
accumulation, the pod-manual shard_map, and the fused AMSGrad stream. The
communication round itself — rule LHS/RHS, staleness cap, eq. 3 innovation
aggregation, quantize hook, accounting — is
:func:`repro.core.comm.comm_round`, the SAME core the reference engine
(core/engine.py) runs, so the two implementations of Algorithm 1 cannot
drift. Per-rule behaviour (eq. 5/7/10 and beyond-paper rules) lives in the
:mod:`repro.core.comm` strategy objects; there is no rule dispatch here.

Everything is a single pjit'd step: per-worker gradients are a `vmap` over
the M-leading axis (sharded over the worker axis of the mesh), per-worker
stale state is stored with that same M-leading sharding so each worker's
copy lives on its own slice of the machine, and the server's AMSGrad update
runs redundantly on every chip (standard SPMD "virtual server").

State-memory policy knobs (production necessities for the 314B/405B archs):
  * ``cada_dtype``   — storage dtype of {∇ (nabla), per-worker stale trees};
    comm_round casts the innovation to this dtype BEFORE the cross-worker
    mean, so it is the wire format of the gated collective (bf16 halves
    DCN bytes — LAQ-adjacent, beyond-paper)
  * ``microbatches`` — gradient accumulation inside the step (activation
    memory /= microbatches at fixed global batch)
  * ``moments_dtype`` — {h, v̂} storage on the flat plane (bf16 halves the
    8P-byte moment footprint; math stays fp32 — kernels/cada_update.py)
  * ``state_fsdp_axes`` / ``shard_cada_state`` / FSDP — ZeRO the FLAT
    state planes over those mesh axes (see ``flat_state_axes``): the
    (n_flat,) server planes split into equal contiguous shards, the
    (M, n_flat) worker planes shard worker axis × remaining state axes,
    and the fused kernels run shard-local with psum'd scalar reductions.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import flat as F
from repro.core.comm import (CommState, comm_round, comm_state_specs,
                             init_comm_state, nabla_f32, record_progress,
                             strategy_for)
from repro.core.rules import CommRule
from repro.kernels import ops as kops
from repro.launch.mesh import DATA, POD
from repro.models.config import ModelConfig
from repro.models.model import abstract_params, init_params, lm_loss
from repro.distributed.sharding import (FlatSharding, param_pspecs,
                                        to_named, wants_fsdp)


@dataclass(frozen=True)
class TrainHParams:
    rule: CommRule = field(default_factory=lambda: CommRule(kind="cada2"))
    lr: float = 1e-4
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    microbatches: int = 1
    cada_dtype: str = "float32"     # nabla / stale-tree storage
    moments_dtype: str = "float32"  # {h, v̂} storage (bf16 = beyond-paper;
    #   lives on the flat plane: the fused kernel is dtype-parametric)
    fused: bool = True              # flat-buffer state plane + fused
    #   AMSGrad/CADA server update (core/flat.py) — the ONLY state plane:
    #   every sharding policy (FSDP, ZeRO'd/data-sharded state, bf16
    #   moments) runs on sharded flat planes (see flat_state_axes).
    #   fused=False is an explicit DEBUG flag selecting the per-leaf
    #   pytree reference implementation (the readable oracle the parity
    #   gates pin the flat plane against).
    fsdp: bool | None = None        # None = auto (sharding.wants_fsdp)
    fsdp_axes: tuple = ("data",)    # params: gathered per layer per micro
    state_fsdp_axes: tuple = ()     # () = same as fsdp_axes. Set to
    #   ("data","pod") to ZeRO the OPTIMIZER state across pods while params
    #   stay pod-local: state is touched once per step, so the pod-spanning
    #   reshard rides DCN once — vs per-layer-per-microbatch param gathers
    #   (measured 1.9e3 s/step on llama3-405b — §Perf).
    shard_cada_state: bool = False  # shard nabla/stale trees over "data"
    #                                 even when params don't FSDP (§Perf)
    group_evals: bool = False       # second eval as ≤R broadcast-point
    #   evaluations grouped by stale-iterate ring slot (indexed rules on
    #   the flat plane). Weight traffic M× → R×, arithmetic × occupancy —
    #   opt in when the eval is weight-bandwidth-bound and R ≪ M.

    @property
    def cada_jnp_dtype(self):
        return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
            self.cada_dtype]

    @property
    def moments_jnp_dtype(self):
        return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
            self.moments_dtype]


class DistTrainState(NamedTuple):
    step: jnp.ndarray        # k
    params: Any              # θ^k
    h: Any                   # first moment (fp32)
    vhat: Any                # running max second moment (fp32)
    comm: Any                # CommState (None for stateless rules: the
    #                          'always' baseline keeps no innovation state)


# ------------------------------------------------------------------- specs

def worker_axis_name(mesh) -> str:
    return POD if POD in mesh.shape else DATA


def flat_state_axes(cfg: ModelConfig, mesh, hp: TrainHParams) -> tuple:
    """Mesh axes the (n_flat,) flat SERVER planes (θ̂/h/v̂/∇) shard over.

    Resolution order mirrors the reference plane's memory policy:
    explicit ``state_fsdp_axes`` (ZeRO the state wider than the params —
    e.g. ("data", "pod") on the 314B/405B archs), then
    ``shard_cada_state`` (("data",)), then the param FSDP axes when FSDP
    is on (explicitly or by ``sharding.wants_fsdp`` size auto-detection),
    else replicate. Axes absent from the mesh (or of size 1) are dropped,
    so the same hparams resolve sanely on every mesh.
    """
    if not hp.fused:
        return ()
    if hp.state_fsdp_axes:
        axes = hp.state_fsdp_axes
    elif hp.shard_cada_state:
        axes = (DATA,)
    elif hp.fsdp or (hp.fsdp is None and wants_fsdp(cfg, mesh)):
        axes = hp.fsdp_axes
    else:
        return ()
    return tuple(a for a in axes if a in mesh.shape and mesh.shape[a] > 1)


def flat_sharding(cfg: ModelConfig, mesh, hp: TrainHParams) -> FlatSharding:
    """The resolved :class:`sharding.FlatSharding` for (cfg, mesh, hp) —
    the ONE object the layout pad divisor (``.shards``), the plane specs
    (``.col_axes`` / ``.server_spec``), and the shard-local kernels all
    read, so they cannot disagree. ``axes`` is empty when no state
    sharding applies (every property then degrades to the unsharded
    form)."""
    return FlatSharding(mesh=mesh, waxis=worker_axis_name(mesh),
                        axes=flat_state_axes(cfg, mesh, hp))


def flat_state_shards(cfg: ModelConfig, mesh, hp: TrainHParams) -> int:
    """State-shard count of the flat plane on ``mesh`` — the divisor
    ``FlatLayout.n_flat`` is padded to. Pass this as ``shards=`` to
    ``init_train_state`` / ``abstract_train_state`` when pairing them with
    ``jit_train_step`` (which resolves it from the same mesh): the state
    structures must agree."""
    return flat_sharding(cfg, mesh, hp).shards


def flat_layout(cfg: ModelConfig, shards: int = 1) -> F.FlatLayout:
    """The trainer's flat layout for ``cfg`` at a given state-shard count
    (checkpoint tooling uses this to reshard across shard counts)."""
    return F.layout_of(abstract_params(cfg), shards=shards)


def _strip_axis(spec: P, axis: str) -> P:
    """Remove ``axis`` from every dim of a PartitionSpec."""
    dims = []
    for d in spec:
        if d == axis:
            dims.append(None)
        elif isinstance(d, tuple):
            kept = tuple(a for a in d if a != axis)
            dims.append(kept if kept else None)
        else:
            dims.append(d)
    return P(*dims)


def _prepend_worker(specs, axis: str):
    """(M, ...)-leading per-worker tree: worker axis leads; inner dims keep
    their param sharding minus the worker axis (no axis may repeat)."""
    return jax.tree.map(
        lambda s: P(axis, *_strip_axis(s, axis)), specs,
        is_leaf=lambda x: isinstance(x, P))


def train_state_specs(cfg: ModelConfig, mesh, hp: TrainHParams
                      ) -> DistTrainState:
    psp = param_pspecs(cfg, mesh, hp.fsdp, hp.fsdp_axes)
    waxis = worker_axis_name(mesh)
    strategy = strategy_for(hp.rule)
    if hp.fused:
        # flat plane: gradient-shaped state needs only two spec shapes —
        # (n_flat,) server planes sharded over the state axes (ZeRO) and
        # worker-leading (M, n_flat) planes sharded worker axis × the
        # remaining state axes; parameter-shaped extras keep param specs.
        fs = flat_sharding(cfg, mesh, hp)
        return DistTrainState(
            step=P(),
            params=psp,
            h=fs.server_spec(), vhat=fs.server_spec(),
            comm=(None if strategy.stateless else
                  F.flat_comm_state_specs(
                      strategy, psp, _prepend_worker(psp, waxis),
                      waxis, P, state_axes=fs.axes,
                      col_axes=fs.col_axes)),
        )
    wsp = _prepend_worker(psp, waxis)
    # optimizer moments may ZeRO over more axes than params (see hparams)
    msp = (param_pspecs(cfg, mesh, True, hp.state_fsdp_axes)
           if hp.state_fsdp_axes else psp)
    # gradient-shaped CADA state has no compute locality: shard it over
    # every axis available regardless of the params' FSDP choice (§Perf —
    # cuts the cross-pod innovation all-reduce per-chip volume).
    gsp = (param_pspecs(cfg, mesh, True, ("data",))
           if hp.shard_cada_state else psp)
    gwsp = _prepend_worker(gsp, waxis)
    return DistTrainState(
        step=P(),
        params=psp,
        h=msp, vhat=msp,
        comm=(None if strategy.stateless else
              comm_state_specs(strategy, psp, wsp, gsp, gwsp, P(None))),
    )


def train_batch_specs(mesh, local_steps: int = 1) -> dict:
    """Worker-split batch: leaves are (M, b_m, ...); M shards over the
    worker axis, b_m over 'data' on the multi-pod mesh (where the worker is
    a whole pod). M-RoPE positions are (M, 3, b_m, S). With
    ``local_steps`` H > 1 (delta-payload rules) every leaf gains a leading
    replicated local-step axis: (H, M, b_m, ...)."""
    waxis = worker_axis_name(mesh)
    inner = DATA if waxis == POD else None
    lead = (None,) if local_steps > 1 else ()

    def spec_for(key, ndim):
        ndim -= len(lead)
        if key == "positions":
            return P(*lead, waxis, None, inner, *(None,) * (ndim - 3))
        return P(*lead, waxis, inner, *(None,) * (ndim - 2))

    return spec_for


def worker_split(batch: dict, m: int, local_steps: int = 1) -> dict:
    """Global batch -> (M, b_m, ...) per-worker leading axis (positions:
    (3, B, S) -> (M, 3, b_m, S)). ``local_steps`` H > 1 (delta-payload
    rules) carves the global batch into H per-local-step slices FIRST:
    (H, M, b_m, ...) with b_m = B / (H · M) — one round consumes the same
    global sample count whatever the payload cadence."""
    hm = local_steps * m
    out = {}
    for key, leaf in batch.items():
        if key == "positions":
            three, b = leaf.shape[0], leaf.shape[1]
            rest = leaf.shape[2:]
            split = leaf.reshape((three, hm, b // hm) + rest).swapaxes(0, 1)
        else:
            b = leaf.shape[0]
            split = leaf.reshape((hm, b // hm) + leaf.shape[1:])
        out[key] = (split.reshape((local_steps, m) + split.shape[1:])
                    if local_steps > 1 else split)
    return out


def worker_split_abstract(batch: dict, m: int, local_steps: int = 1
                          ) -> dict:
    """ShapeDtypeStruct version of ``worker_split`` (dry-run path)."""
    lead = (local_steps,) if local_steps > 1 else ()
    hm = local_steps * m
    out = {}
    for key, leaf in batch.items():
        if key == "positions":
            three, b = leaf.shape[0], leaf.shape[1]
            shp = lead + (m, three, b // hm) + leaf.shape[2:]
        else:
            b = leaf.shape[0]
            shp = lead + (m, b // hm) + leaf.shape[1:]
        out[key] = jax.ShapeDtypeStruct(shp, leaf.dtype)
    return out


# ------------------------------------------------------------------- state

def init_train_state(cfg: ModelConfig, hp: TrainHParams, m: int, rng,
                     shards: int = 1) -> DistTrainState:
    """``shards`` is the flat-plane state-shard count (pad divisor of
    ``n_flat``). Mesh-free callers keep the default 1; when pairing with
    ``jit_train_step`` pass ``flat_state_shards(cfg, mesh, hp)`` so the
    state structure matches the compiled step's."""
    params = init_params(cfg, rng)
    strategy = strategy_for(hp.rule)
    # h and v̂ are allocated as DISTINCT buffers throughout: the jitted
    # step donates the state, and aliased leaves trip XLA's
    # donate-the-same-buffer-twice check.
    if hp.fused:
        layout = F.layout_of(params, shards=shards)
        return DistTrainState(
            step=jnp.zeros([], jnp.int32),
            params=params,
            h=jnp.zeros((layout.n_flat,), hp.moments_jnp_dtype),
            vhat=jnp.zeros((layout.n_flat,), hp.moments_jnp_dtype),
            comm=(None if strategy.stateless else
                  F.init_flat_comm_state(strategy, layout, params, m,
                                         grad_dtype=hp.cada_jnp_dtype)),
        )

    def zeros_m():
        return jax.tree.map(
            lambda p: jnp.zeros(p.shape, hp.moments_jnp_dtype), params)
    return DistTrainState(
        step=jnp.zeros([], jnp.int32),
        params=params,
        h=zeros_m(), vhat=zeros_m(),
        comm=(None if strategy.stateless else
              init_comm_state(strategy, params, m,
                              grad_dtype=hp.cada_jnp_dtype)),
    )


def place_train_state(state: DistTrainState, mesh, sspecs
                      ) -> DistTrainState:
    """Commit ``state`` to the shardings ``jit_train_step`` compiled for
    (``sspecs`` is its second result). The jitted step refuses committed
    arguments laid out otherwise, and arrays made under ``jax.set_mesh``
    are committed replicated."""
    return jax.device_put(state, jax.tree.map(
        lambda s: to_named(mesh, s), sspecs,
        is_leaf=lambda x: isinstance(x, P)))


def abstract_train_state(cfg: ModelConfig, hp: TrainHParams, m: int,
                         shards: int = 1):
    return jax.eval_shape(
        partial(init_train_state, cfg, hp, m, shards=shards),
        jax.random.PRNGKey(0))


# -------------------------------------------------------------------- step

def _amsgrad_apply(params, h, vhat, grad, hp: TrainHParams):
    """The paper's (2a)-(2c) in sharded jnp (XLA fuses the stream); returns
    (params', h', vhat', ||Δθ||²). Math in fp32; storage dtype follows the
    incoming state (hp.moments_dtype)."""
    h_new = jax.tree.map(
        lambda m, g: (hp.b1 * m.astype(jnp.float32)
                      + (1 - hp.b1) * g.astype(jnp.float32)).astype(m.dtype),
        h, grad)
    vhat_new = jax.tree.map(
        lambda s, g: jnp.maximum(
            hp.b2 * s.astype(jnp.float32)
            + (1 - hp.b2) * jnp.square(g.astype(jnp.float32)),
            s.astype(jnp.float32)).astype(s.dtype),
        vhat, grad)
    upd = jax.tree.map(
        lambda m, s: (-hp.lr * m.astype(jnp.float32)
                      / jnp.sqrt(hp.eps + s.astype(jnp.float32))),
        h_new, vhat_new)
    new_params = jax.tree.map(
        lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype),
        params, upd)
    dsq = sum(jnp.sum(jnp.square(u)) for u in jax.tree.leaves(upd))
    return new_params, h_new, vhat_new, dsq


def make_pod_vgrads(cfg: ModelConfig, hp: TrainHParams, mesh):
    """Per-worker gradients as a PARTIAL-AUTO shard_map: manual over the
    pod axis, auto (GSPMD) over data/model.

    A plain `vmap` over the worker axis lets the partitioner replicate the
    per-pod gradient computation across pods (measured: 2-4× total-flop
    inflation on the 2×16×16 mesh — §Perf). The manual pod axis makes the
    locality structural: each pod can only ever compute its own worker's
    gradient.
    """
    psp = param_pspecs(cfg, mesh, hp.fsdp, hp.fsdp_axes)

    def manual_only(spec):
        dims = []
        for d in spec:
            if d == POD:
                dims.append(POD)
            elif isinstance(d, tuple) and POD in d:
                dims.append(POD)
            else:
                dims.append(None)
        return P(*dims)

    params_in = jax.tree.map(manual_only, psp,
                             is_leaf=lambda x: isinstance(x, P))
    wparams_in = jax.tree.map(lambda s: P(POD, *s), params_in,
                              is_leaf=lambda x: isinstance(x, P))

    def _shardmapped(f, in_specs):
        return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=(P(POD), P(POD)), axis_names={POD},
                             check_vma=False)

    def make(worker_grad):
        def body_bcast(params, batch):
            wb = jax.tree.map(lambda x: x[0], batch)
            loss, g = worker_grad(params, wb)
            return (jnp.asarray(loss)[None],
                    jax.tree.map(lambda x: x[None], g))

        def body_per(wparams, batch):
            wp = jax.tree.map(lambda x: x[0], wparams)
            wb = jax.tree.map(lambda x: x[0], batch)
            loss, g = worker_grad(wp, wb)
            return (jnp.asarray(loss)[None],
                    jax.tree.map(lambda x: x[None], g))

        vgrad = _shardmapped(body_bcast, (params_in, P(POD)))
        vgrad_per = _shardmapped(body_per, (wparams_in, P(POD)))
        return vgrad, vgrad_per

    return make


def make_worker_grad(cfg: ModelConfig, hp: TrainHParams,
                     micro_constrain=None):
    """One worker's mean LM gradient, with microbatch accumulation —
    shared by the dense mesh step and the federated cohort step, so the
    two planes compute identical per-worker gradients."""
    if micro_constrain is None:
        micro_constrain = lambda mb: mb  # noqa: E731

    def loss_fn(params, wbatch):
        return lm_loss(cfg, params, wbatch)[0]

    def worker_grad(params, wbatch):
        bm = jax.tree.leaves(wbatch)[0].shape[0]
        nm = min(hp.microbatches, bm)
        while bm % nm:  # largest feasible count <= requested (static)
            nm -= 1
        if nm == 1:
            return jax.value_and_grad(loss_fn)(params, wbatch)

        def split(leaf, batch_axis=0):
            b = leaf.shape[batch_axis]
            return leaf.reshape(leaf.shape[:batch_axis] + (nm, b // nm)
                                + leaf.shape[batch_axis + 1:])

        mb = micro_constrain(
            {k: (split(v, 1).swapaxes(0, 1) if k == "positions"
                 else split(v)) for k, v in wbatch.items()})

        def acc(carry, micro):
            loss_a, g_a = carry
            loss, g = jax.value_and_grad(loss_fn)(params, micro)
            return (loss_a + loss,
                    jax.tree.map(jnp.add, g_a, g)), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss_s, g_s), _ = jax.lax.scan(acc, (0.0, zeros), mb)
        return loss_s / nm, jax.tree.map(lambda g: g / nm, g_s)

    return worker_grad


def make_train_step(cfg: ModelConfig, hp: TrainHParams, m: int,
                    wconstrain=None, vgrad_factory=None,
                    micro_constrain=None, shards: int = 1,
                    flat_shard=None):
    """Pure (state, batch) -> (state, metrics) hierarchical-CADA step.

    ``batch`` leaves carry an (M,)-leading worker axis. Shard with
    ``train_state_specs`` / ``train_batch_specs`` and wrap in jax.jit.
    ``wconstrain`` (optional) pins per-worker gradient trees via
    with_sharding_constraint; ``vgrad_factory`` (optional, from
    ``make_pod_vgrads``) replaces the worker vmap with a pod-manual
    shard_map; ``micro_constrain`` (optional) re-pins the data-axis
    sharding after the microbatch reshape — without it GSPMD partially
    replicates the per-pod batch (measured 4× flop inflation — §Perf).
    ``shards`` / ``flat_shard`` (a ``sharding.FlatSharding``) describe the
    flat state plane's sharding: the layout pads to ``shards`` equal
    slices and the fused kernels + LHS norms run shard-local with psum'd
    scalars. Mesh-free callers leave both at their defaults (unsharded
    plane, plain whole-plane ops).

    On the device every phase of the step carries a ``jax.named_scope``:
    ``cada.grad_eval`` (the workers' forward and backward passes),
    ``cada.pack`` (pytree <-> flat plane), ``cada.rule_state`` (the
    rule's evaluation-point state), ``cada.gate`` (LHS, RHS, upload mask),
    ``cada.eq3`` (the aggregate) and ``cada.server_update`` (AMSGrad and
    the RHS history). The innermost name lands in each compiled
    instruction's ``op_name``, so a profile splits the step by phase.
    """
    strategy = strategy_for(hp.rule)
    if wconstrain is None:
        wconstrain = lambda t: t  # noqa: E731

    worker_grad = make_worker_grad(cfg, hp, micro_constrain)

    if vgrad_factory is not None:
        vgrad_raw, vgrad_per_raw = vgrad_factory(worker_grad)
    else:
        vgrad_raw = jax.vmap(worker_grad, in_axes=(None, 0))
        vgrad_per_raw = jax.vmap(worker_grad, in_axes=(0, 0))

    def vgrad(params, batch):
        losses, grads = vgrad_raw(params, batch)
        return losses, wconstrain(grads)

    def vgrad_per(wparams, batch):
        losses, grads = vgrad_per_raw(wparams, batch)
        return losses, wconstrain(grads)

    use_flat = hp.fused
    if use_flat:
        layout = F.layout_of(abstract_params(cfg), shards=shards)
        # the stacked two-point evaluation (fresh + second as a broadcast
        # 2-way eval axis, batch NOT copied — flat.stacked_two_point_eval)
        # applies only on the vmap route: the pod-manual shard_map pins
        # the M-leading axis in its in-specs. Since the broadcast-axis
        # rewrite it wins on CPU as well (see CADAEngine's fuse_evals
        # note), so it is on wherever it applies — matching the engine's
        # default keeps the parity contract bit-exact.
        fuse_evals = vgrad_factory is None

        def fused_update(pflat, h, vhat, grad_flat):
            """Fused AMSGrad/CADA server update on the packed plane —
            Pallas on TPU, fused flat jnp elsewhere (kernels/ops.py);
            shard-local with one psum'd ‖Δθ‖² when the plane is sharded."""
            theta, h2, vh2, dsq = kops.fused_amsgrad_flat(
                pflat, h, vhat, grad_flat, hp.lr,
                b1=hp.b1, b2=hp.b2, eps=hp.eps, shard=flat_shard)
            return layout.unpack(layout.cast_roundtrip(theta)), h2, vh2, dsq

        def pack_server(params):
            """θ^k packed onto the (possibly ZeRO-sharded) server plane."""
            pflat = layout.pack(params)
            if flat_shard is not None:
                pflat = flat_shard.constrain_server(pflat)
            return pflat

    # ------------- stateless rules (always ⇒ distributed Adam/AMSGrad):
    # no innovation state is materialized — the production path for the
    # 314B/405B single-pod fallback, where M stale gradient copies would
    # not fit in HBM.
    if strategy.stateless:
        def step_always(state: DistTrainState, batch):
            with jax.named_scope("cada.grad_eval"):
                losses, fresh = vgrad(state.params, batch)
            if use_flat:
                with jax.named_scope("cada.eq3"):
                    grad_flat = jnp.mean(layout.pack_worker(fresh), axis=0)
                    if flat_shard is not None:
                        grad_flat = flat_shard.constrain_server(grad_flat)
                pflat = pack_server(state.params)
                with jax.named_scope("cada.server_update"):
                    params, h, vhat, dsq = fused_update(
                        pflat, state.h, state.vhat, grad_flat)
            else:
                grad = jax.tree.map(lambda g: jnp.mean(g, axis=0), fresh)
                params, h, vhat, dsq = _amsgrad_apply(
                    state.params, state.h, state.vhat, grad, hp)
            new_state = state._replace(step=state.step + 1, params=params,
                                       h=h, vhat=vhat)
            return new_state, {
                "loss": jnp.mean(losses),
                "uploads": jnp.asarray(m, jnp.int32),
                "skip_rate": jnp.zeros([], jnp.float32),
                "upload_mask": jnp.ones((m,), bool),
                "staleness": jnp.ones((m,), jnp.int32),
                "dtheta_sq": dsq,
            }
        return step_always

    # ------------- rules with innovation state: the shared Algorithm-1
    # core drives the round; this function only applies the server update.
    # Delta-payload rules (local_momentum / fedadam) ride the SAME path:
    # the round returns the mean accumulated model delta as nabla, and the
    # trainer's fused AMSGrad server consumes it — the "FedAMSGrad"
    # variant (server momentum over deltas; the engine/sim planes run the
    # rules' prescribed sgd(1.0)/Adam servers — parity oracles live
    # there, not here). Batches then carry a leading (H,) local-step axis
    # (``worker_split(..., local_steps=H)``).
    if use_flat:
        def step_flat(state: DistTrainState, batch):
            k = state.step
            pflat = pack_server(state.params)
            out = F.flat_comm_round(
                strategy, layout, state.comm, state.params, pflat, batch,
                k, vgrad=vgrad, vgrad_per=vgrad_per, fuse_evals=fuse_evals,
                group_evals=hp.group_evals, shard=flat_shard)
            with jax.named_scope("cada.server_update"):
                params, h, vhat, dsq = fused_update(
                    pflat, state.h, state.vhat, F.nabla_f32(out.comm))
                comm = F.record_progress(out.comm, dsq, k)
            new_state = DistTrainState(step=k + 1, params=params, h=h,
                                       vhat=vhat, comm=comm)
            metrics = {"loss": jnp.mean(out.losses), "dtheta_sq": dsq,
                       **out.metrics}
            return new_state, metrics

        return step_flat

    def step(state: DistTrainState, batch):
        k = state.step
        out = comm_round(strategy, state.comm, state.params, batch, k,
                         vgrad=vgrad, vgrad_per=vgrad_per)
        params, h, vhat, dsq = _amsgrad_apply(
            state.params, state.h, state.vhat, nabla_f32(out.comm), hp)
        comm = record_progress(out.comm, dsq, k)
        new_state = DistTrainState(step=k + 1, params=params, h=h,
                                   vhat=vhat, comm=comm)
        metrics = {"loss": jnp.mean(out.losses), "dtheta_sq": dsq,
                   **out.metrics}
        return new_state, metrics

    return step


# ------------------------------------------------------- federated cohort

class CohortTrainState(NamedTuple):
    """Trainer state on the cohort-virtualized plane: the (M, n_flat)
    per-worker planes live in a host :class:`repro.core.flat.WorkerPool`;
    this holds only O(n) server planes + O(M) scalar vectors."""
    step: jnp.ndarray
    params: Any
    h: jnp.ndarray           # (n_flat,) first moment
    vhat: jnp.ndarray        # (n_flat,) running max second moment
    server: Any              # flat.CohortServerState
    params_flat: jnp.ndarray


def init_cohort_train_state(cfg: ModelConfig, hp: TrainHParams, m: int,
                            rng, *, pool_storage: str = "ram",
                            pool_path: str | None = None):
    """(CohortTrainState, WorkerPool) for M federated workers — device
    memory O(n), host pool O(M·n) (``pool_storage="memmap"`` +
    ``pool_path`` spill it past RAM). Requires the fused plane (the
    cohort round is a flat-plane op; there is no per-leaf cohort oracle
    at the trainer layer — core/flat.py's dense plane is the parity
    oracle)."""
    if not hp.fused:
        raise ValueError("the cohort plane requires fused=True")
    params = init_params(cfg, rng)
    layout = F.layout_of(params)
    params_flat = layout.pack(params)
    strategy = strategy_for(hp.rule)
    server, pool = F.init_cohort_state(
        strategy, layout, params, m, grad_dtype=hp.cada_jnp_dtype,
        params_flat=params_flat, pool_storage=pool_storage,
        pool_path=pool_path)
    state = CohortTrainState(
        step=jnp.zeros([], jnp.int32), params=params,
        h=jnp.zeros((layout.n_flat,), hp.moments_jnp_dtype),
        vhat=jnp.zeros((layout.n_flat,), hp.moments_jnp_dtype),
        server=server, params_flat=params_flat)
    return state, pool


def make_cohort_train_step(cfg: ModelConfig, hp: TrainHParams, m: int):
    """Mesh-free federated LM step: (state, pool, batch, cohort) ->
    (state, metrics).

    Per round only the C sampled workers' rows move: gather from the host
    pool, one :func:`repro.core.flat.flat_comm_round`-equivalent cohort
    round (bit-exact to the dense plane with the cohort's participation
    mask), the fused AMSGrad server update, scatter back. ``batch`` holds
    ONLY cohort rows ((C, b, ...) leaves — at federated M a dense
    (M, b, ·) batch is itself the memory wall). The jitted step donates
    state and rows, so the device never holds two cohort planes.
    Gradients come from the same ``make_worker_grad`` as the mesh step
    (microbatch accumulation included)."""
    if not hp.fused:
        raise ValueError("the cohort plane requires fused=True")
    strategy = strategy_for(hp.rule)
    layout = F.layout_of(abstract_params(cfg))
    worker_grad = make_worker_grad(cfg, hp)
    vgrad = jax.vmap(worker_grad, in_axes=(None, 0))
    vgrad_per = jax.vmap(worker_grad, in_axes=(0, 0))

    built = {}

    def fused_step_for(pool):
        """The jitted fused-block step bound to ``pool``'s plane layout
        (stacking order + storage dtype) — built once per layout. Shared
        by the eager ``train_step`` and the pipelined driver."""
        if pool.plane_dtype is None:
            raise ValueError("the cohort step needs a uniform-dtype pool")
        order, dtype = pool.plane_order, pool.plane_dtype
        key = (order, np.dtype(dtype).str)
        if built.get("key") == key:
            return built["step"]

        def step(state: CohortTrainState, fused, batch, cohort):
            k = state.step
            rows = F.split_fused_rows(fused, order)
            out = F.flat_cohort_round(
                strategy, layout, state.server, rows, state.params,
                state.params_flat, batch, k, cohort, m_total=m,
                vgrad=vgrad, vgrad_per=vgrad_per, fuse_evals=True)
            with jax.named_scope("cada.server_update"):
                theta, h, vhat, dsq = kops.fused_amsgrad_flat(
                    state.params_flat, state.h, state.vhat,
                    out.server.nabla.astype(jnp.float32), hp.lr,
                    b1=hp.b1, b2=hp.b2, eps=hp.eps)
                theta = layout.cast_roundtrip(theta)
                server = F.record_progress(out.server, dsq, k)
            new_state = CohortTrainState(
                step=k + 1, params=layout.unpack(theta), h=h, vhat=vhat,
                server=server, params_flat=theta)
            metrics = {"loss": jnp.mean(out.losses), "dtheta_sq": dsq,
                       **out.metrics}
            return new_state, F.stack_fused_rows(out.rows, order,
                                                 dtype), metrics

        built["key"] = key
        built["step"] = jax.jit(step, donate_argnums=(0, 1))
        return built["step"]

    def train_step(state: CohortTrainState, pool, batch, cohort):
        cohort = np.sort(np.asarray(cohort).astype(np.int32))
        jitted = fused_step_for(pool)
        fused = pool.gather_fused(cohort)
        state, out, metrics = jitted(state, fused, batch,
                                     jnp.asarray(cohort))
        pool.scatter_fused(cohort, out)
        return state, metrics

    train_step.fused_step_for = fused_step_for
    return train_step


def run_cohort_train(train_step, state: CohortTrainState, pool, batches,
                     cohorts, *, pipeline: bool = True,
                     metrics_every: int = 8, trace=None,
                     metrics_out: list | None = None):
    """Multi-round cohort driver for the trainer — the federated analogue
    of ``CADAEngine.run_cohort``. ``train_step`` is the callable from
    :func:`make_cohort_train_step`; ``batches`` is a list/tuple of
    per-round cohort batches or a callable ``batches(i, cohort)``.
    ``pipeline=True`` double-buffers transfers (bit-exact to the serial
    ``pipeline=False`` oracle); metrics are fetched every
    ``metrics_every`` rounds. ``trace`` (an ``obs.trace.Tracer`` or
    None) records per-round pipeline spans; ``metrics_out`` (a list)
    receives fetched metrics incrementally, surviving mid-run
    exceptions. Returns (state, list-of-metric-dicts)."""
    cohorts = np.asarray(cohorts, np.int32)
    if callable(batches):
        batch_fn = batches
    else:
        batch_fn = lambda i, _c: batches[i]                 # noqa: E731
    return F.run_cohort_rounds(
        train_step.fused_step_for(pool), state, pool, batch_fn, cohorts,
        pipeline=pipeline, metrics_every=metrics_every, trace=trace,
        metrics_out=metrics_out)


def jit_train_step(cfg: ModelConfig, mesh, hp: TrainHParams):
    """jit the step with explicit in/out shardings for ``mesh``.

    Returns (jitted_step, state_specs, m). Metrics are replicated.
    """
    waxis = worker_axis_name(mesh)
    m = mesh.shape[waxis]
    sspecs = train_state_specs(cfg, mesh, hp)
    # flat-plane sharding: resolved ONCE here, threaded through the layout
    # (pad divisor), the specs above, and the shard-local kernel forms.
    fs = flat_sharding(cfg, mesh, hp)
    shards = fs.shards
    flat_shard = fs if (hp.fused and fs.axes) else None

    # NOTE: constraining the vmapped gradient trees directly
    # (with_sharding_constraint to the worker_grads specs) was measured to
    # be a no-op for locality AND trips an XLA SPMD-partitioner CHECK when
    # combined with data-sharded CADA state — micro_constrain below is the
    # effective (and stable) mechanism. The pod-manual shard_map is opt-in:
    # it crashes the XLA partitioner when combined with FSDP param specs
    # (spmd_partitioner_util.cc:504 CHECK), so it is enabled only for
    # non-FSDP configs.
    use_podmap = (waxis == POD
                  and not (hp.fsdp
                           or (hp.fsdp is None and wants_fsdp(cfg, mesh))))
    vgrad_factory = make_pod_vgrads(cfg, hp, mesh) if use_podmap else None

    def micro_constrain(mb):
        if waxis != POD:
            return mb  # single-pod: the worker IS the data group

        def spec_for(key, ndim):
            if key == "positions":
                return P(None, None, DATA, *(None,) * (ndim - 3))
            return P(None, DATA, *(None,) * (ndim - 2))

        return {k: jax.lax.with_sharding_constraint(
                    v, to_named(mesh, spec_for(k, v.ndim)))
                for k, v in mb.items()}

    step = make_train_step(
        cfg, hp, m,
        vgrad_factory=vgrad_factory, micro_constrain=micro_constrain,
        shards=shards, flat_shard=flat_shard)
    sshard = jax.tree.map(lambda s: to_named(mesh, s), sspecs,
                          is_leaf=lambda x: isinstance(x, P))
    # delta-payload rules feed (H, M, b_m, ...) batches (worker_split with
    # local_steps) — the local-step axis is a replicated leading dim
    spec_for = train_batch_specs(
        mesh, hp.rule.local_steps
        if strategy_for(hp.rule).delta_payload else 1)

    def batch_shardings(batch_sds):
        return {k: to_named(mesh, spec_for(k, v.ndim))
                for k, v in batch_sds.items()}

    def make(batch_sds):
        # the state argument is donated: launch/train.py threads it
        # linearly, so the (potentially huge) buffers alias in place
        return jax.jit(step,
                       in_shardings=(sshard, batch_shardings(batch_sds)),
                       out_shardings=(sshard, None),
                       donate_argnums=(0,))

    return make, sspecs, m
