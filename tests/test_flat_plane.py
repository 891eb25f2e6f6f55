"""Flat-buffer state plane (core/flat.py): layout round-tripping, the
batched-LHS kernels vs the pytree oracles, flat quantization equivalence,
fused-vs-reference engine parity for every registered rule (Pallas kernels
exercised in interpret mode), and donation aliasing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import flat as F
from repro.core.comm import per_worker_sq_norm, strategy_for
from repro.core.quantize import per_worker_quantize_dequantize
from repro.core.rules import RULES, CommRule
from repro.kernels import cada_update as _cu
from repro.kernels import ops as kops


def _mixed_tree(rng, bf16=True):
    return {
        "w": jnp.asarray(rng.normal(size=(13, 7)), jnp.float32),
        "e": jnp.asarray(rng.normal(size=(5, 3, 2)),
                         jnp.bfloat16 if bf16 else jnp.float32),
        "b": jnp.asarray(rng.normal(size=(11,)), jnp.float32),
        "s": jnp.asarray(rng.normal(size=()), jnp.float32),
    }


# ------------------------------------------------------------------ layout

@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.booleans())
def test_pack_unpack_roundtrip_property(seed, bf16):
    """pack -> unpack is exact for every leaf (incl. bf16 storage), and
    the padded tail is identically zero."""
    rng = np.random.default_rng(seed)
    tree = _mixed_tree(rng, bf16=bf16)
    layout = F.layout_of(tree)
    buf = layout.pack(tree)
    assert buf.shape == (layout.n_flat,) and layout.n_flat % F.PAD_ALIGN == 0
    assert layout.n == sum(np.prod(l.shape, dtype=int)
                           for l in jax.tree.leaves(tree))
    np.testing.assert_array_equal(np.asarray(buf[layout.n:]), 0.0)
    back = layout.unpack(buf)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_pack_worker_roundtrip(rng):
    m = 4
    tree = _mixed_tree(rng)
    wtree = jax.tree.map(
        lambda l: jnp.stack([l + i for i in range(m)]), tree)
    layout = F.layout_of(tree)
    plane = layout.pack_worker(wtree)
    assert plane.shape == (m, layout.n_flat)
    back = layout.unpack_worker(plane)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(wtree)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_cast_roundtrip_matches_pack_of_unpack(rng):
    """cast_roundtrip(buf) == pack(unpack(buf)) — the invariant that keeps
    the engine's carried flat params consistent with the trainer's
    re-packed ones under reduced-precision leaves."""
    tree = _mixed_tree(rng, bf16=True)
    layout = F.layout_of(tree)
    buf = layout.pack(tree) + 1e-4  # perturb off exact bf16 values
    rt = layout.cast_roundtrip(buf)
    np.testing.assert_array_equal(
        np.asarray(rt[:layout.n]),
        np.asarray(layout.pack(layout.unpack(buf))[:layout.n]))
    # the padding tail passes through untouched
    np.testing.assert_array_equal(np.asarray(rt[layout.n:]),
                                  np.asarray(buf[layout.n:]))
    # all-fp32 layouts: a no-op (object identity — no ops inserted)
    t32 = _mixed_tree(rng, bf16=False)
    l32 = F.layout_of(t32)
    b32 = l32.pack(t32)
    assert l32.cast_roundtrip(b32) is b32


# --------------------------------------------------------- batched kernels

def test_batched_diff_sq_norm_kernel_vs_oracle(rng):
    """The batched one-pass Pallas kernel (interpret mode) computes all M
    per-worker ||a_m − b_m||² exactly like per_worker_sq_norm."""
    m, n = 3, 2 * _cu.BLOCK
    a = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    got = _cu.batched_diff_sq_norm_flat(a, b, interpret=True)
    want = per_worker_sq_norm({"x": a - b})
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)

    got1 = _cu.batched_sq_norm_flat(a, interpret=True)
    want1 = per_worker_sq_norm({"x": a})
    np.testing.assert_allclose(np.asarray(got1), np.asarray(want1),
                               rtol=1e-5)


def test_batched_wrappers_pad_arbitrary_widths(rng):
    """kernels/ops.py wrappers accept any flat width (satellite: no
    n % BLOCK restriction) on both the jnp and interpret-Pallas routes."""
    m, n = 4, 1234
    a = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    want = np.sum((np.asarray(a) - np.asarray(b)) ** 2, axis=1)
    for interpret in (None, True):
        got = kops.batched_diff_sq_norm(a, b, interpret=interpret)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4)


def test_fused_amsgrad_flat_arbitrary_length(rng):
    """ops.fused_amsgrad_flat pads through to the kernel for any n —
    logreg-sized buffers take the fused route too (satellite 1)."""
    from repro.kernels import ref
    n = 777
    theta = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.asarray(rng.normal(size=n) * 0.1, jnp.float32)
    vhat = jnp.abs(jnp.asarray(rng.normal(size=n) * 0.01, jnp.float32))
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    want = ref.amsgrad_ref(theta, h, vhat, g, 0.01)
    for interpret in (None, True):
        got = kops.fused_amsgrad_flat(theta, h, vhat, g, 0.01,
                                      interpret=interpret)
        for a, b in zip(got, want):
            assert np.asarray(a).shape == np.asarray(b).shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


# ------------------------------------------------------- flat quantization

def test_flat_quantize_matches_pytree_quantize(rng):
    """Per-(worker, leaf-segment) scales on the flat plane are bit-equal
    to the pytree per-worker quantizer (the wire-format sync property)."""
    m = 3
    tree = {"w": jnp.asarray(rng.normal(size=(m, 6, 2)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(m, 4)), jnp.float32)}
    layout = F.layout_of({"w": tree["w"][0], "b": tree["b"][0]})
    plane = layout.pack_worker(tree)
    for bits in (2, 4, 8):
        q_flat = F.per_worker_quantize_dequantize_flat(layout, plane, bits)
        q_tree = per_worker_quantize_dequantize(tree, bits)
        np.testing.assert_array_equal(
            np.asarray(q_flat), np.asarray(layout.pack_worker(q_tree)))
    # padded tail survives untouched
    np.testing.assert_array_equal(
        np.asarray(F.per_worker_quantize_dequantize_flat(
            layout, plane, 4)[:, layout.n:]),
        np.asarray(plane[:, layout.n:]))


def test_flat_topk_matches_pytree_topk(rng):
    """Per-(worker, leaf-segment) top-k on the flat plane is bit-equal to
    the pytree sparsifier (same threshold rule over the same entries),
    and the padded tail passes through untouched."""
    from repro.core.quantize import per_worker_topk_sparsify
    m = 3
    tree = {"w": jnp.asarray(rng.normal(size=(m, 6, 2)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(m, 4)), jnp.float32)}
    layout = F.layout_of({"w": tree["w"][0], "b": tree["b"][0]})
    plane = layout.pack_worker(tree)
    for frac in (0.1, 0.5, 1.0):
        s_flat = F.per_worker_topk_sparsify_flat(layout, plane, frac)
        s_tree = per_worker_topk_sparsify(tree, frac)
        np.testing.assert_array_equal(
            np.asarray(s_flat), np.asarray(layout.pack_worker(s_tree)))
    np.testing.assert_array_equal(
        np.asarray(F.per_worker_topk_sparsify_flat(
            layout, plane, 0.25)[:, layout.n:]),
        np.asarray(plane[:, layout.n:]))


# ------------------------------------- fused vs reference engine parity

def _small_problem(m):
    from repro.data.partition import pad_to_matrix, uniform_partition
    from repro.data.synthetic import ijcnn1_like
    from repro.core.engine import make_sampler
    from repro.models.small import logreg_init, logreg_loss
    ds = ijcnn1_like(n=400)
    mtx = pad_to_matrix(uniform_partition(ds.n, m, seed=0))
    sample = make_sampler(ds.x, ds.y, mtx, 16)
    return logreg_loss, logreg_init(None, 22, 2), sample


@pytest.mark.parametrize("kind", RULES)
def test_fused_engine_matches_reference_engine(kind):
    """The flat-plane hot path and the per-leaf reference implementation
    of Algorithm 1 agree per iteration for EVERY registered rule — masks
    exactly, parameters numerically — with the Pallas kernels running in
    interpret mode on the fused side."""
    from repro.core.engine import CADAEngine
    from repro.optim.fused import FusedAMSGrad
    m, steps = 3, 8
    loss_fn, params, sample = _small_problem(m)
    # c chosen so adaptive rules produce a MIXED mask over the run
    rule = CommRule(kind=kind, c=5.0, d_max=4, max_delay=6)
    batches = jax.vmap(sample)(jax.random.split(jax.random.PRNGKey(2),
                                                steps))
    eng_f = CADAEngine(loss_fn, FusedAMSGrad(lr=0.05), rule, m,
                       interpret=True)
    eng_r = CADAEngine(loss_fn, FusedAMSGrad(lr=0.05), rule, m,
                       fused=False)
    stf, mf = jax.jit(eng_f.run)(eng_f.init(params), batches)
    str_, mr = jax.jit(eng_r.run)(eng_r.init(params), batches)
    np.testing.assert_array_equal(np.asarray(mf["upload_mask"]),
                                  np.asarray(mr["upload_mask"]))
    np.testing.assert_array_equal(np.asarray(mf["staleness"]),
                                  np.asarray(mr["staleness"]))
    np.testing.assert_allclose(np.asarray(mf["bytes_up"]),
                               np.asarray(mr["bytes_up"]), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(stf.params),
                    jax.tree.leaves(str_.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-6)


def test_fused_engine_mixed_mask_meta():
    """Meta-check: the parity setup above exercises BOTH branches (uploads
    and skips) for cada2 — all-upload trajectories would prove less."""
    from repro.core.engine import CADAEngine
    from repro.optim.fused import FusedAMSGrad
    m, steps = 3, 8
    loss_fn, params, sample = _small_problem(m)
    rule = CommRule(kind="cada2", c=5.0, d_max=4, max_delay=6)
    batches = jax.vmap(sample)(jax.random.split(jax.random.PRNGKey(2),
                                                steps))
    eng = CADAEngine(loss_fn, FusedAMSGrad(lr=0.05), rule, m)
    _, mets = jax.jit(eng.run)(eng.init(params), batches)
    total = int(np.asarray(mets["uploads"]).sum())
    assert 0 < total < steps * m, total


# ---------------------------------------------------------------- donation

def test_donated_engine_state_aliases():
    """donate_argnums on the jitted run actually aliases the state buffers
    (verified on the compiled module — a donated-but-copied state would
    show zero aliases), and the undonated version shows none for the
    matching param buffers only."""
    from repro.core.engine import CADAEngine
    from repro.optim.fused import FusedAMSGrad
    from repro.utils.hlo_cost import donation_aliases
    m, steps = 3, 4
    loss_fn, params, sample = _small_problem(m)
    rule = CommRule(kind="cada2", c=0.6, d_max=4, max_delay=6)
    batches = jax.vmap(sample)(jax.random.split(jax.random.PRNGKey(0),
                                                steps))
    eng = CADAEngine(loss_fn, FusedAMSGrad(lr=0.05), rule, m)
    st = eng.init(params)
    donated = jax.jit(eng.run, donate_argnums=(0,)).lower(
        st, batches).compile()
    assert donation_aliases(donated.as_text()) > 0
    plain = jax.jit(eng.run).lower(st, batches).compile()
    assert donation_aliases(plain.as_text()) == 0
    # the donated executable still runs and matches the plain one
    out_d, _ = donated(jax.tree.map(lambda x: x.copy(), st), batches)
    out_p, _ = plain(st, batches)
    for a, b in zip(jax.tree.leaves(out_d.params),
                    jax.tree.leaves(out_p.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


# ------------------------------------------------------- sharded layout

needs_mesh8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(the CI mesh matrix leg)")


@pytest.mark.parametrize("shards", [1, 2, 4, 8])
def test_layout_shards_pad_and_roundtrip(rng, shards):
    """n_flat pads to a multiple of align·shards; per-shard split/merge
    and pack/unpack round-trip bit-exactly; the true entries are invariant
    to the shard count (only the padding tail moves)."""
    tree = _mixed_tree(rng)
    lay = F.layout_of(tree, shards=shards)
    assert lay.shards == shards
    assert lay.n_flat % (F.PAD_ALIGN * shards) == 0
    assert lay.shard_len * shards == lay.n_flat
    buf = lay.pack(tree)
    np.testing.assert_array_equal(
        np.asarray(lay.shard_merge(lay.shard_split(buf))), np.asarray(buf))
    back = lay.unpack(buf)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    # shard-count independence of the true prefix
    base = F.layout_of(tree)
    np.testing.assert_array_equal(np.asarray(buf[:lay.n]),
                                  np.asarray(base.pack(tree)[:base.n]))
    # (M, n_flat) planes split the same way
    wtree = jax.tree.map(lambda l: jnp.stack([l, l + 1]), tree)
    plane = lay.pack_worker(wtree)
    np.testing.assert_array_equal(
        np.asarray(lay.shard_merge(lay.shard_split(plane))),
        np.asarray(plane))


def test_spec_dim():
    from jax.sharding import PartitionSpec as P
    assert F.spec_dim(()) is None
    assert F.spec_dim(("data",)) == "data"
    assert F.spec_dim(("data", "pod")) == ("data", "pod")
    assert P(F.spec_dim(("data",))) == P("data")


def test_fused_amsgrad_bf16_moments_matches_per_leaf_reference(rng):
    """Dtype-parametric moments: the fused kernel (jnp fallback AND
    interpret-mode Pallas) with bf16 {h, v̂} matches the per-leaf reference
    stream's dtype discipline — the STORED (rounded) moment drives the
    update."""
    from repro.distributed.trainer import TrainHParams, _amsgrad_apply
    n = 700
    theta = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.asarray(rng.normal(size=n) * 0.1, jnp.bfloat16)
    vhat = jnp.abs(jnp.asarray(rng.normal(size=n) * 0.01, jnp.bfloat16))
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    hp = TrainHParams(lr=0.01, moments_dtype="bfloat16")
    want_p, want_h, want_v, want_sq = _amsgrad_apply(
        {"x": theta}, {"x": h}, {"x": vhat}, {"x": g}, hp)
    for interpret in (None, True):
        t2, h2, v2, sq = kops.fused_amsgrad_flat(theta, h, vhat, g, 0.01,
                                                 interpret=interpret)
        assert h2.dtype == jnp.bfloat16 and v2.dtype == jnp.bfloat16
        # θ to 1-2 ulp (separately-jitted programs fuse the update stream
        # differently); the STORED moments must round identically
        np.testing.assert_allclose(np.asarray(t2),
                                   np.asarray(want_p["x"]), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(h2, np.float32),
                                      np.asarray(want_h["x"], np.float32))
        np.testing.assert_array_equal(np.asarray(v2, np.float32),
                                      np.asarray(want_v["x"], np.float32))
        np.testing.assert_allclose(float(sq), float(want_sq), rtol=1e-6)


# --------------------------------------------------- shard-local kernels

def _mesh_shard(shape, axes, waxis, saxes):
    from repro.distributed.sharding import FlatSharding
    from repro.launch.mesh import make_mesh
    mesh = make_mesh(shape, axes)
    return mesh, FlatSharding(mesh=mesh, waxis=waxis, axes=saxes)


@needs_mesh8
def test_sharded_fused_update_matches_unsharded(rng):
    """The shard_map'd fused update (each device one n_flat/S slice, one
    psum'd ‖Δθ‖²) equals the whole-plane form."""
    mesh, shard = _mesh_shard((8, 1), ("data", "model"), "data", ("data",))
    n = 8 * 32
    theta = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.asarray(rng.normal(size=n) * 0.1, jnp.float32)
    vhat = jnp.abs(jnp.asarray(rng.normal(size=n) * 0.01, jnp.float32))
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    want = kops.fused_amsgrad_flat(theta, h, vhat, g, 0.01)
    got = jax.jit(lambda *a: kops.fused_amsgrad_flat(
        *a, 0.01, shard=shard))(theta, h, vhat, g)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


@needs_mesh8
@pytest.mark.parametrize("mesh_kind", ["rows", "rows_and_cols"])
def test_sharded_batched_norms_match_oracle(rng, mesh_kind):
    """The shard-local batched LHS forms (manual over worker rows, psum
    over column shards) equal the plain whole-plane kernels — on a
    worker-axis-only mesh and on a pod×data mesh where the flat dim itself
    shards (the pod-mesh layout)."""
    if mesh_kind == "rows":
        mesh, shard = _mesh_shard((8, 1), ("data", "model"), "data",
                                  ("data",))
        m = 8
    else:
        mesh, shard = _mesh_shard((2, 4), ("pod", "data"), "pod",
                                  ("data",))
        m = 2
    n = 4 * 24
    a = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(jax.jit(lambda x, y: kops.batched_diff_sq_norm(
            x, y, shard=shard))(a, b)),
        np.asarray(kops.batched_diff_sq_norm(a, b)), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(jax.jit(lambda x: kops.batched_sq_norm(
            x, shard=shard))(a)),
        np.asarray(kops.batched_sq_norm(a)), rtol=1e-5)


# -------------------------------------------------------- sparse topk wire

def test_topk_sparse_wire_extract_scatter_roundtrip(rng):
    """(values, indices) extraction from a sparsified plane reconstructs
    it bit-exactly (tie-free data), padding tail untouched."""
    tree = {"w": jnp.zeros((6, 2), jnp.float32),
            "b": jnp.zeros((4,), jnp.float32)}
    layout = F.layout_of(tree)
    plane = jnp.asarray(rng.normal(size=(3, layout.n_flat)), jnp.float32)
    sp = F.per_worker_topk_sparsify_flat(layout, plane, 0.3)
    vals, idx = F.per_worker_topk_extract_flat(layout, sp, 0.3)
    dense = F.sparse_rows_to_dense(idx, vals, layout.n_flat)
    np.testing.assert_array_equal(np.asarray(dense[:, :layout.n]),
                                  np.asarray(sp[:, :layout.n]))
    # fixed payload size: K = Σ_seg ⌈frac·s⌉
    from repro.core.quantize import topk_count
    K = sum(topk_count(s, 0.3) for s in layout.sizes)
    assert vals.shape == idx.shape == (3, K)


def test_topk_sparse_wire_parity_with_dense(rng):
    """Satellite gate: the topk rule with ``sparse_wire=True`` (the (v, i)
    pairs ride the simulated collective) reproduces the dense-wire run
    bit-exactly — identical masks, staleness, bytes, and parameters."""
    from repro.core.engine import CADAEngine
    from repro.optim.fused import FusedAMSGrad
    m, steps = 3, 8
    loss_fn, params, sample = _small_problem(m)
    batches = jax.vmap(sample)(jax.random.split(jax.random.PRNGKey(2),
                                                steps))
    runs = {}
    for sparse in (False, True):
        rule = CommRule(kind="topk", c=5.0, d_max=4, max_delay=6,
                        topk_frac=0.25, sparse_wire=sparse)
        eng = CADAEngine(loss_fn, FusedAMSGrad(lr=0.05), rule, m)
        runs[sparse] = jax.jit(eng.run)(eng.init(params), batches)
    std, md = runs[False]
    sts, ms = runs[True]
    np.testing.assert_array_equal(np.asarray(ms["upload_mask"]),
                                  np.asarray(md["upload_mask"]))
    np.testing.assert_array_equal(np.asarray(ms["staleness"]),
                                  np.asarray(md["staleness"]))
    np.testing.assert_allclose(np.asarray(ms["bytes_up"]),
                               np.asarray(md["bytes_up"]), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(sts.params),
                    jax.tree.leaves(std.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_avp_compose_gates_on_energy(rng):
    """Satellite: ``avp_compose`` composes the period gate with the CADA
    LHS. Pointwise the composed gate is a SUBSET of the plain one
    (the energy check can only veto), so up to and including the FIRST
    iteration where the two trajectories' masks differ, composed ⊆
    plain — after that the states diverge and no global ordering holds.
    The max-staleness cap still forces uploads."""
    from repro.core.engine import CADAEngine
    from repro.optim.fused import FusedAMSGrad
    m, steps = 3, 10
    loss_fn, params, sample = _small_problem(m)
    batches = jax.vmap(sample)(jax.random.split(jax.random.PRNGKey(4),
                                                steps))
    mets = {}
    for compose in (False, True):
        rule = CommRule(kind="avp", c=5.0, d_max=4, max_delay=6,
                        period_min=2, period_max=4, avp_compose=compose)
        eng = CADAEngine(loss_fn, FusedAMSGrad(lr=0.05), rule, m)
        _, mets[compose] = jax.jit(eng.run)(eng.init(params), batches)
    plain = np.asarray(mets[False]["upload_mask"])
    comp = np.asarray(mets[True]["upload_mask"])
    for i in range(steps):
        assert not (comp[i] & ~plain[i]).any(), \
            f"composed gate added an upload at iteration {i}"
        if not np.array_equal(comp[i], plain[i]):
            break  # trajectories diverge from here on
    else:
        pytest.fail("compose never vetoed an upload — the test setup "
                    "does not exercise the composition")
    assert int(comp.sum()) > 0  # the staleness cap still forces uploads
    # flat plane and pytree reference agree on the composed gate too
    rule = CommRule(kind="avp", c=5.0, d_max=4, max_delay=6,
                    period_min=2, period_max=4, avp_compose=True)
    eng_r = CADAEngine(loss_fn, FusedAMSGrad(lr=0.05), rule, m,
                       fused=False)
    _, mr = jax.jit(eng_r.run)(eng_r.init(params), batches)
    np.testing.assert_array_equal(np.asarray(mets[True]["upload_mask"]),
                                  np.asarray(mr["upload_mask"]))


def test_donated_trainer_step_aliases():
    """The trainer's jitted step with donated state aliases too (the
    launch/train.py and benchmarks/run.py hot loops)."""
    import repro.configs as C
    from repro.distributed.trainer import (TrainHParams, init_train_state,
                                           make_train_step, worker_split)
    from repro.utils.hlo_cost import donation_aliases
    cfg = C.get_smoke_config("stablelm-1.6b")
    hp = TrainHParams(rule=CommRule(kind="cada2", c=0.5, d_max=4,
                                    max_delay=10), lr=1e-3)
    m = 2
    step = jax.jit(make_train_step(cfg, hp, m), donate_argnums=(0,))
    st = init_train_state(cfg, hp, m, jax.random.PRNGKey(0))
    batch = worker_split(
        {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                      cfg.vocab)}, m)
    compiled = step.lower(st, batch).compile()
    assert donation_aliases(compiled.as_text()) > 0
