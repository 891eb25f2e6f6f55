"""Engine ↔ trainer parity: the guardrail for the shared Algorithm-1 core.

Both `core/engine.py` (vmap-simulated workers) and `distributed/trainer.py`
(pod runtime) consume the SAME Algorithm-1 core; this test pins that
contract on the DEFAULT configuration of both — the flat-buffer state
plane with the fused AMSGrad/CADA server update (core/flat.py +
optim/fused.py): on identical data, for EVERY rule, identical
per-iteration upload masks, staleness vectors, and (numerically) identical
parameter trajectories. The per-leaf reference pair (fused=False engine vs
non-fused trainer) is pinned for cada2 as the oracle-side guardrail.

The SHARDED leg (needs an 8-device forced-host mesh — the CI mesh matrix
leg sets XLA_FLAGS=--xla_force_host_platform_device_count=8) pins the
fused flat plane under ZeRO'd state (``state_fsdp_axes=("data",)``)
against the per-leaf pytree reference for EVERY rule: `_flat_enabled` is
gone, so these hparams now run the fused sharded plane, and the masks /
staleness must be bit-identical to the reference. The policy-knob tests
(bf16 moments, explicit FSDP, ZeRO'd state) run mesh-free on any device
count — the configurations that used to fall back to the per-leaf path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.core.engine import CADAEngine
from repro.core.rules import RULES, CommRule
from repro.distributed.trainer import (TrainHParams, flat_state_shards,
                                       init_train_state, jit_train_step,
                                       make_train_step, place_train_state,
                                       worker_split)
from repro.launch.mesh import make_mesh
from repro.models.model import init_params, lm_loss
from repro.optim.adam import adam
from repro.optim.fused import FusedAMSGrad

CFG = C.get_smoke_config("stablelm-1.6b")
M = 2
STEPS = 6
LR = 1e-3

needs_mesh8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(the CI mesh matrix leg)")


def _loss_fn(params, wbatch):
    return lm_loss(CFG, params, wbatch)[0]


def _batches():
    return [worker_split(
        {"tokens": jax.random.randint(jax.random.PRNGKey(100 + i),
                                      (4, 33), 0, CFG.vocab)}, M)
        for i in range(STEPS)]


def _run_engine(rule, fused=True):
    # FusedAMSGrad IS the trainer's fused stream; the reference pair uses
    # adam() whose defaults match it: amsgrad=True, eps inside the sqrt,
    # no bias correction (paper eqs. 2a-2c)
    if fused:
        eng = CADAEngine(_loss_fn, FusedAMSGrad(lr=LR), rule, M)
    else:
        eng = CADAEngine(_loss_fn, adam(lr=LR), rule, M, fused=False)
    st = eng.init(init_params(CFG, jax.random.PRNGKey(0)))
    step = jax.jit(eng.step)
    mets = []
    for b in _batches():
        st, m = step(st, b)
        mets.append(m)
    return st, mets


def _run_trainer(rule, fused=True):
    hp = TrainHParams(rule=rule, lr=LR, fused=fused)
    step = jax.jit(make_train_step(CFG, hp, M))
    st = init_train_state(CFG, hp, M, jax.random.PRNGKey(0))
    mets = []
    for b in _batches():
        st, m = step(st, b)
        mets.append(m)
    return st, mets


def _assert_parity(kind, emets, tmets, est, tst):

    for i, (em, tm) in enumerate(zip(emets, tmets)):
        np.testing.assert_array_equal(
            np.asarray(em["upload_mask"]), np.asarray(tm["upload_mask"]),
            err_msg=f"{kind}: upload mask diverged at iteration {i}")
        np.testing.assert_array_equal(
            np.asarray(em["staleness"]), np.asarray(tm["staleness"]),
            err_msg=f"{kind}: staleness diverged at iteration {i}")
        assert int(em["uploads"]) == int(tm["uploads"])

    for a, b in zip(jax.tree.leaves(est.params),
                    jax.tree.leaves(tst.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("kind", RULES)
def test_engine_and_trainer_identical_per_iteration(kind):
    """Default (fused flat-plane) engine vs default trainer — all rules.

    c chosen so the mask is MIXED over the run (some uploads, some skips)
    for the adaptive rules — parity on all-upload trajectories alone
    would not exercise the stale branches.
    """
    rule = CommRule(kind=kind, c=20.0, d_max=4, max_delay=10)
    est, emets = _run_engine(rule)
    tst, tmets = _run_trainer(rule)
    _assert_parity(kind, emets, tmets, est, tst)


def test_reference_pair_parity_cada2():
    """The per-leaf reference implementations stay in lockstep too."""
    rule = CommRule(kind="cada2", c=20.0, d_max=4, max_delay=10)
    est, emets = _run_engine(rule, fused=False)
    tst, tmets = _run_trainer(rule, fused=False)
    _assert_parity("cada2-ref", emets, tmets, est, tst)


def test_adaptive_rules_actually_skip_in_this_setup():
    """Meta-check: the parity run exercises BOTH branches (uploads and
    skips) for the adaptive rules — otherwise the test above proves less
    than it claims."""
    rule = CommRule(kind="cada2", c=20.0, d_max=4, max_delay=10)
    _, emets = _run_engine(rule)
    total = sum(int(m["uploads"]) for m in emets)
    assert 0 < total < STEPS * M, total


# ------------------------------------------------- sharding-policy parity
# The hparams that used to force the per-leaf fallback (_flat_enabled) now
# run the fused flat plane; each must still match the per-leaf reference.

POLICIES = {
    "bf16_moments": dict(moments_dtype="bfloat16"),
    "fsdp": dict(fsdp=True),
    "zero_state": dict(state_fsdp_axes=("data",)),
}


def _run_trainer_hp(hp, m, batches):
    step = jax.jit(make_train_step(CFG, hp, m))
    st = init_train_state(CFG, hp, m, jax.random.PRNGKey(0))
    mets = []
    for b in batches:
        st, mm = step(st, b)
        mets.append(mm)
    return st, mets


@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_policy_knobs_run_fused_and_match_reference(policy):
    """Mesh-free: bf16 moments / FSDP / ZeRO'd-state hparams run the flat
    plane (h is a single (n_flat,) buffer) and match the per-leaf
    reference per iteration."""
    rule = CommRule(kind="cada2", c=20.0, d_max=4, max_delay=10)
    kw = POLICIES[policy]
    hp_f = TrainHParams(rule=rule, lr=LR, **kw)
    hp_r = TrainHParams(rule=rule, lr=LR, fused=False, **kw)
    batches = _batches()
    stf, mf = _run_trainer_hp(hp_f, M, batches)
    assert stf.h.ndim == 1, "flat plane expected (no fallback fork left)"
    if policy == "bf16_moments":
        assert stf.h.dtype == jnp.bfloat16
    str_, mr = _run_trainer_hp(hp_r, M, batches)
    _assert_parity(f"cada2-{policy}", mf, mr, stf, str_)


@needs_mesh8
@pytest.mark.parametrize("kind", RULES)
def test_fused_sharded_matches_reference_all_rules(kind):
    """The acceptance gate: fused flat plane with ZeRO'd state on an
    8-device (data=8, model=1) mesh vs the per-leaf pytree reference, for
    EVERY rule — upload masks and staleness bit-identical, parameters
    numerically identical. Quantized-wire rules (cinn/laq) get a wider
    parameter tolerance: the mesh partitions the gradient reductions, and
    one-ulp gradient differences flip quantization buckets (a full
    quantization step, ~1e-4·scale), while the Algorithm-1 decisions stay
    exact."""
    mesh = make_mesh((8, 1), ("data", "model"))
    m, steps = 8, 4
    rule = CommRule(kind=kind, c=20.0, d_max=4, max_delay=10)
    batches = [worker_split(
        {"tokens": jax.random.randint(jax.random.PRNGKey(100 + i),
                                      (8, 33), 0, CFG.vocab)}, m)
        for i in range(steps)]

    hp_s = TrainHParams(rule=rule, lr=LR, state_fsdp_axes=("data",))
    make, sspecs, mm = jit_train_step(CFG, mesh, hp_s)
    assert mm == m
    sds = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       batches[0])
    with jax.set_mesh(mesh):
        step = make(sds)
        st = place_train_state(
            init_train_state(CFG, hp_s, m, jax.random.PRNGKey(0),
                             shards=flat_state_shards(CFG, mesh, hp_s)),
            mesh, sspecs)
        ms = []
        for b in batches:
            st, met = step(st, b)
            ms.append(met)
    # the server planes actually shard over the data axis
    assert st.h.sharding.spec[0] == "data"

    hp_r = TrainHParams(rule=rule, lr=LR, fused=False)
    str_, mr = _run_trainer_hp(hp_r, m, batches)

    for i, (a, b) in enumerate(zip(ms, mr)):
        np.testing.assert_array_equal(
            np.asarray(a["upload_mask"]), np.asarray(b["upload_mask"]),
            err_msg=f"{kind}: sharded mask diverged at iteration {i}")
        np.testing.assert_array_equal(
            np.asarray(a["staleness"]), np.asarray(b["staleness"]),
            err_msg=f"{kind}: sharded staleness diverged at iteration {i}")
    rtol, atol = ((1e-2, 2e-3) if kind in ("cinn", "laq")
                  else (1e-4, 1e-6))
    for a, b in zip(jax.tree.leaves(st.params),
                    jax.tree.leaves(str_.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=rtol, atol=atol)


@needs_mesh8
def test_sharded_parity_mask_is_mixed():
    """Meta-check for the sharded gate: the cada2 run above exercises both
    uploads and skips."""
    mesh = make_mesh((8, 1), ("data", "model"))
    m, steps = 8, 4
    rule = CommRule(kind="cada2", c=20.0, d_max=4, max_delay=10)
    hp = TrainHParams(rule=rule, lr=LR, state_fsdp_axes=("data",))
    make, sspecs, _ = jit_train_step(CFG, mesh, hp)
    batches = [worker_split(
        {"tokens": jax.random.randint(jax.random.PRNGKey(100 + i),
                                      (8, 33), 0, CFG.vocab)}, m)
        for i in range(steps)]
    sds = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       batches[0])
    total = 0
    with jax.set_mesh(mesh):
        step = make(sds)
        st = place_train_state(
            init_train_state(CFG, hp, m, jax.random.PRNGKey(0),
                             shards=flat_state_shards(CFG, mesh, hp)),
            mesh, sspecs)
        for b in batches:
            st, met = step(st, b)
            total += int(met["uploads"])
    assert 0 < total < steps * m, total
