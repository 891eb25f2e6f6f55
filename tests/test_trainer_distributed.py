"""Distributed (hierarchical-CADA) trainer: step semantics on the host mesh,
rule equivalences, microbatch invariance, spec plumbing, local-update
baselines."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import repro.configs as C
from repro.core.local_update import LocalUpdateEngine
from repro.core.rules import CommRule
from repro.distributed.trainer import (
    DistTrainState, TrainHParams, flat_state_shards, init_train_state,
    jit_train_step, make_train_step, place_train_state, train_state_specs,
    worker_split, worker_split_abstract,
)
from repro.launch.mesh import make_host_mesh

CFG = C.get_smoke_config("internlm2-1.8b")


def _batch(key, b=8, s=32):
    return {"tokens": jax.random.randint(key, (b, s + 1), 0, CFG.vocab)}


def _steps(kind, n=4, m=4, microbatches=1, c=0.5, seed=0, lr=1e-3):
    hp = TrainHParams(rule=CommRule(kind=kind, c=c, d_max=4, max_delay=10),
                      lr=lr, microbatches=microbatches)
    step = make_train_step(CFG, hp, m)
    st = init_train_state(CFG, hp, m, jax.random.PRNGKey(42))
    step = jax.jit(step)
    outs = []
    for i in range(n):
        batch = worker_split(_batch(jax.random.PRNGKey(seed + i)), m)
        st, mets = step(st, batch)
        outs.append(mets)
    return st, outs


@pytest.mark.parametrize("kind", ["always", "cada1", "cada2", "lag", "cinn"])
def test_step_runs_and_loss_finite(kind):
    st, outs = _steps(kind, n=3)
    for m in outs:
        assert np.isfinite(float(m["loss"]))
    assert int(st.step) == 3


def test_cada2_c0_equals_always():
    """c=0 ⇒ every pod uploads ⇒ trajectory == distributed AMSGrad."""
    st_c, _ = _steps("cada2", n=3, c=0.0)
    st_a, _ = _steps("always", n=3, c=0.0)
    for a, b in zip(jax.tree.leaves(st_c.params),
                    jax.tree.leaves(st_a.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-4)


def test_microbatch_invariance():
    """Gradient accumulation must not change the trajectory (same data)."""
    st1, _ = _steps("always", n=2, microbatches=1)
    st2, _ = _steps("always", n=2, microbatches=2)
    for a, b in zip(jax.tree.leaves(st1.params),
                    jax.tree.leaves(st2.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-4)


def test_huge_c_skips_everything_after_warmup():
    hp = TrainHParams(rule=CommRule(kind="cada2", c=1e12, d_max=4,
                                    max_delay=100))
    m = 4
    step = jax.jit(make_train_step(CFG, hp, m))
    st = init_train_state(CFG, hp, m, jax.random.PRNGKey(0))
    st, mets0 = step(st, worker_split(_batch(jax.random.PRNGKey(1)), m))
    assert int(mets0["uploads"]) == m  # staleness init forces round 0
    st, mets1 = step(st, worker_split(_batch(jax.random.PRNGKey(2)), m))
    assert int(mets1["uploads"]) == 0
    assert float(mets1["skip_rate"]) == 1.0


def test_worker_split_shapes():
    b = {"tokens": jnp.zeros((8, 33), jnp.int32),
         "positions": jnp.zeros((3, 8, 32), jnp.int32)}
    out = worker_split(b, 4)
    assert out["tokens"].shape == (4, 2, 33)
    assert out["positions"].shape == (4, 3, 2, 32)
    sds = worker_split_abstract(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), b), 4)
    assert sds["positions"].shape == (4, 3, 2, 32)


def test_state_specs_structure():
    mesh = make_host_mesh()
    hp = TrainHParams(rule=CommRule(kind="cada2"))
    specs = train_state_specs(CFG, mesh, hp)
    assert isinstance(specs, DistTrainState)
    # per-worker trees lead with the worker axis
    lead = jax.tree.leaves(specs.comm.worker_grads,
                           is_leaf=lambda x: isinstance(x, P))[0]
    assert lead[0] == "data"
    # the strategy owns its extra slices: CADA2 stores the stale-iterate
    # ring (R rows shard like params — replicated leading axis) plus the
    # per-worker slot index and the row versions (both replicated)
    assert set(specs.comm.extras) == {"ring", "slot", "ring_version"}
    ring = jax.tree.leaves(specs.comm.extras["ring"],
                           is_leaf=lambda x: isinstance(x, P))[0]
    assert ring[0] is None
    assert specs.comm.extras["slot"] == P(None)
    # CADA1 stores a snapshot (param-spec'd) + per-worker innovations
    specs_1 = train_state_specs(CFG, mesh, TrainHParams(
        rule=CommRule(kind="cada1")))
    assert set(specs_1.comm.extras) == {"snapshot", "worker_delta"}
    # 'always' is stateless: the whole comm state is dropped
    specs_a = train_state_specs(CFG, mesh, TrainHParams(
        rule=CommRule(kind="always")))
    assert specs_a.comm is None


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8 (the CI mesh matrix leg)")
def test_flat_round_with_manual_shard_maps_on_pod_mesh():
    """The flat state plane on the MULTI-POD mesh (worker = pod): a
    (pod=2, data=4, model=1) mesh with the CADA state sharded over 'data'
    — worker planes shard pod × data, so the batched LHS and the fused
    update run under MANUAL shard_maps over both axes and psum their
    fp32 partials over the column shards. The run must match the
    mesh-free reference's masks.

    The per-worker gradients run under the pod-manual vgrad shard_map
    (``make_pod_vgrads``), nested around the flat round's own manual
    shard_maps; the server plane is pinned straight from the packed
    buffer to its data-sharded layout (``FlatSharding.constrain_server``),
    and ``unpack`` reading it back in order is what the masks check."""
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4, 1), ("pod", "data", "model"))
    hp = TrainHParams(rule=CommRule(kind="cada2", c=20.0, d_max=4,
                                    max_delay=10), lr=1e-3,
                      shard_cada_state=True)
    make, sspecs, m = jit_train_step(CFG, mesh, hp)
    assert m == 2  # the pod is the worker
    batches = [worker_split(_batch(jax.random.PRNGKey(50 + i)), m)
               for i in range(3)]
    sds = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       batches[0])
    mets = []
    with jax.set_mesh(mesh):
        step = make(sds)
        st = place_train_state(
            init_train_state(CFG, hp, m, jax.random.PRNGKey(42),
                             shards=flat_state_shards(CFG, mesh, hp)),
            mesh, sspecs)
        for b in batches:
            st, mm = step(st, b)
            mets.append(mm)
    # worker planes really shard pod × data
    wg = st.comm.worker_grads
    assert tuple(wg.sharding.spec) == ("pod", "data")
    # mesh-free reference trajectory: identical Algorithm-1 decisions
    hp_r = TrainHParams(rule=hp.rule, lr=1e-3, fused=False)
    step_r = jax.jit(make_train_step(CFG, hp_r, m))
    str_ = init_train_state(CFG, hp_r, m, jax.random.PRNGKey(42))
    for i, b in enumerate(batches):
        str_, mr = step_r(str_, b)
        np.testing.assert_array_equal(np.asarray(mets[i]["upload_mask"]),
                                      np.asarray(mr["upload_mask"]),
                                      err_msg=f"pod-map mask at step {i}")
        assert np.isfinite(float(mets[i]["loss"]))


def test_jit_train_step_on_host_mesh():
    mesh = make_host_mesh()
    hp = TrainHParams(rule=CommRule(kind="cada2", c=0.5, d_max=4,
                                    max_delay=10), microbatches=2)
    make, sspecs, m = jit_train_step(CFG, mesh, hp)
    batch = worker_split(_batch(jax.random.PRNGKey(0)), m)
    sds = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       batch)
    with jax.set_mesh(mesh):
        step = make(sds)
        st = place_train_state(
            init_train_state(CFG, hp, m, jax.random.PRNGKey(0),
                             shards=flat_state_shards(CFG, mesh, hp)),
            mesh, sspecs)
        st, mets = step(st, batch)
    assert np.isfinite(float(mets["loss"]))


# ---------------------------------------------------- federated cohort step

def test_cohort_train_step_runs_lm():
    """The mesh-free federated LM step: O(C·n) device rows streamed
    through the host pool, finite losses, first-sampled workers
    force-uploading, and the O(M·n) plane never on device."""
    from repro.core.engine import sample_cohorts
    from repro.distributed.trainer import (init_cohort_train_state,
                                           make_cohort_train_step)
    m, c, rounds = 16, 4, 3
    hp = TrainHParams(rule=CommRule(kind="cada2", c=0.5, d_max=4,
                                    max_delay=10), microbatches=2)
    step = make_cohort_train_step(CFG, hp, m)
    st, pool = init_cohort_train_state(CFG, hp, m, jax.random.PRNGKey(3))
    n_flat = pool.n_flat
    assert pool.nbytes == m * n_flat * 4
    assert pool.device_row_bytes(c) == c * n_flat * 4
    for leaf in jax.tree.leaves((st.server, st.h, st.vhat)):
        assert leaf.shape != (m, n_flat)
    cohorts = sample_cohorts(m, c, rounds, seed=0)
    for k in range(rounds):
        full = _batch(jax.random.PRNGKey(50 + k), b=c * 2)
        batch = worker_split(full, c)        # (C, b_c, ...) cohort rows
        st, mets = step(st, pool, batch, cohorts[k])
        assert np.isfinite(float(mets["loss"]))
        assert mets["upload_mask"].shape == (c,)
    assert int(st.step) == rounds
    # round 0 force-uploads its whole cohort (staleness starts at the cap)
    assert pool.planes["worker_grads"][cohorts[0]].any()
    untouched = np.setdiff1d(np.arange(m), cohorts.ravel())
    if untouched.size:
        assert not pool.planes["worker_grads"][untouched].any()


def test_cohort_train_state_requires_fused():
    from repro.distributed.trainer import (init_cohort_train_state,
                                           make_cohort_train_step)
    hp = TrainHParams(rule=CommRule(kind="cada2"), fused=False)
    with pytest.raises(ValueError, match="fused"):
        init_cohort_train_state(CFG, hp, 4, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="fused"):
        make_cohort_train_step(CFG, hp, 4)


# --------------------------------------------------- local-update baselines

def test_local_update_baselines_converge():
    from repro.data.partition import pad_to_matrix, uniform_partition
    from repro.data.synthetic import ijcnn1_like
    from repro.core.engine import make_sampler
    from repro.models.small import logreg_init, logreg_loss

    ds = ijcnn1_like(n=1000)
    mtx = pad_to_matrix(uniform_partition(ds.n, 4, 0))
    sample = make_sampler(ds.x, ds.y, mtx, 16)
    params = logreg_init(None, 22, 2)
    for algo in ("local_momentum", "fedadam"):
        eng = LocalUpdateEngine(logreg_loss, n_workers=4, h_period=5,
                                algo=algo, lr=0.05, server_lr=0.05)
        st = eng.init(params)
        rngs = jax.random.split(jax.random.PRNGKey(0), 30 * 5)
        batches = jax.vmap(sample)(rngs)
        batches = jax.tree.map(
            lambda x: x.reshape((30, 5) + x.shape[1:]), batches)
        st, mets = jax.jit(eng.run)(st, batches)
        losses = np.asarray(mets["loss"])  # (rounds, H)
        assert losses[-1].mean() < losses[0].mean() * 0.8, algo
        assert int(np.asarray(mets["uploads"]).sum()) == 30 * 4
