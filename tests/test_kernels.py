"""Per-kernel allclose vs the ref.py pure-jnp oracles, swept over shapes and
dtypes (interpret=True executes the kernel body in Python on CPU)."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.cada_update import BLOCK


def _rand(rng, shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(rng.normal(size=shape) * scale).astype(dtype)


# ------------------------------------------------------- fused AMSGrad/CADA

@pytest.mark.parametrize("nblocks", [1, 2, 3])
@pytest.mark.parametrize("theta_dtype", [jnp.float32, jnp.bfloat16])
def test_fused_amsgrad_matches_ref(rng, nblocks, theta_dtype):
    n = nblocks * BLOCK
    theta = _rand(rng, n).astype(theta_dtype)
    h = _rand(rng, n, scale=0.1)
    vhat = jnp.abs(_rand(rng, n, scale=0.01))
    g = _rand(rng, n)
    out_k = ops.fused_amsgrad_flat(theta, h, vhat, g, 0.01, interpret=True)
    out_r = ref.amsgrad_ref(theta, h, vhat, g, 0.01)
    for a, b in zip(out_k, out_r):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-5)


def test_fused_amsgrad_respects_hparams(rng):
    n = BLOCK
    theta, h = _rand(rng, n), _rand(rng, n, scale=0.1)
    vhat, g = jnp.abs(_rand(rng, n, scale=0.01)), _rand(rng, n)
    for b1, b2, eps, lr in [(0.8, 0.99, 1e-6, 0.1), (0.0, 0.999, 1e-8, 1.0)]:
        out_k = ops.fused_amsgrad_flat(theta, h, vhat, g, lr, b1=b1, b2=b2,
                                       eps=eps, interpret=True)
        out_r = ref.amsgrad_ref(theta, h, vhat, g, lr, b1=b1, b2=b2, eps=eps)
        for a, b in zip(out_k, out_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


def test_fused_amsgrad_vhat_monotone(rng):
    """AMSGrad invariant: v̂ never decreases."""
    n = BLOCK
    theta = _rand(rng, n)
    h = jnp.zeros(n)
    vhat = jnp.abs(_rand(rng, n, scale=0.01))
    for step in range(3):
        g = _rand(rng, n, scale=10.0 ** -step)
        theta, h, vhat_new, _ = ops.fused_amsgrad_flat(
            theta, h, vhat, g, 0.01, interpret=True)
        assert bool(jnp.all(vhat_new >= vhat - 1e-7))
        vhat = vhat_new


def test_diff_sq_norm_matches_ref(rng):
    for nblocks in (1, 4):
        n = nblocks * BLOCK
        a, b = _rand(rng, n), _rand(rng, n)
        d = ops.diff_sq_norm_flat(a, b, interpret=True)
        np.testing.assert_allclose(float(d), float(ref.diff_sq_norm_ref(a, b)),
                                   rtol=1e-5)


@partial(jax.jit, static_argnames=("m_total",))
def _eq3_row_mean_loop(plane, m_total, base=None):
    """Oracle: eq. (3)'s order-fixed row sum as a ``fori_loop`` from +0.0
    over the rows in descending order, over ``m_total``, added to
    ``base`` in the same program when one is given."""
    plane = plane.astype(jnp.float32)
    rows = plane.shape[0]
    zero = jnp.zeros(plane.shape[1:], jnp.float32)
    mean = jax.lax.fori_loop(
        0, rows, lambda i, acc: acc + plane[rows - 1 - i], zero) / m_total
    return mean if base is None else base + mean


@pytest.mark.parametrize("interpret", [None, True], ids=["jnp", "kernel"])
@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("rows", [1, 2, 4, 64])
def test_eq3_row_mean_matches_order_fixed_loop(rng, rows, masked, interpret):
    """The unrolled chain of static row slices — in jnp and in the Pallas
    kernel's body — gives the loop's bits, zero signs included, over
    planes with −0.0 entries and, when ``masked``, half the rows zeroed
    to +0.0 as the upload mask does; so does its sum into a base plane.
    The jnp form's program has no loop left."""
    n = 50_000
    x = rng.normal(size=(rows, n)) * 10.0 ** rng.uniform(-3, 3, (rows, n))
    x[:, :64] = -0.0                                  # an all −0.0 column
    x[rng.uniform(size=(rows, n)) < 0.05] = -0.0      # scattered −0.0
    if masked:
        x[np.arange(rows) % 2 == 0] = 0.0
    plane = jnp.asarray(x, jnp.float32)
    base = _rand(rng, n, scale=1e-3)
    m_total = rows + 3
    want = _eq3_row_mean_loop(plane, m_total)
    got = ops.eq3_row_mean(plane, m_total, interpret=interpret)
    np.testing.assert_array_equal(np.asarray(got).view(np.int32),
                                  np.asarray(want).view(np.int32))
    got = ops.eq3_row_mean(plane, m_total, base, interpret=interpret)
    want = _eq3_row_mean_loop(plane, m_total, base)
    np.testing.assert_array_equal(np.asarray(got).view(np.int32),
                                  np.asarray(want).view(np.int32))
    if interpret is None:
        lowered = jax.jit(ops.eq3_row_mean, static_argnums=1).lower(
            plane, m_total)
        assert "while" not in lowered.as_text()
        assert "while" not in lowered.compile().as_text()


def test_pytree_fused_update_roundtrip(rng):
    """Mixed-dtype pytree: shapes/dtypes survive; padding is inert."""
    tree = {"w": _rand(rng, (300, 77), jnp.bfloat16),
            "b": _rand(rng, (33,), jnp.float32)}
    zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), tree)
    g = jax.tree.map(lambda x: jnp.ones(x.shape, jnp.float32) * 0.5, tree)
    p, h, vhat, sq = ops.fused_cada_update(tree, zeros, zeros, g, 0.1,
                                           interpret=True)
    assert p["w"].dtype == jnp.bfloat16 and p["b"].dtype == jnp.float32
    assert p["w"].shape == (300, 77)
    # fp32 oracle over the same tree
    _, _, _, sq_ref = ref.amsgrad_ref(
        jnp.zeros(300 * 77 + 33), jnp.zeros(300 * 77 + 33),
        jnp.zeros(300 * 77 + 33), jnp.full(300 * 77 + 33, 0.5), 0.1)
    np.testing.assert_allclose(float(sq), float(sq_ref), rtol=1e-5)


# ----------------------------------------------------------- selective scan

@pytest.mark.parametrize("shape", [(1, 64, 128, 16), (2, 128, 256, 16),
                                   (3, 64, 128, 64)])
def test_selective_scan_matches_ref(rng, shape):
    g, s, d, n = shape
    dt = jnp.abs(_rand(rng, (g, s, d), scale=0.1))
    x = _rand(rng, (g, s, d))
    a = -jnp.abs(_rand(rng, (g, d, n)))
    b = _rand(rng, (g, s, n))
    c = _rand(rng, (g, s, n))
    y_k, hf_k = ops.selective_scan(dt, x, a, b, c, chunk=32, dblk=128,
                                   interpret=True)
    y_r, hf_r = ref.selective_scan_ref(dt, x, a, b, c)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(hf_k), np.asarray(hf_r),
                               rtol=2e-4, atol=2e-4)


def test_selective_scan_bf16_inputs(rng):
    g, s, d, n = 1, 64, 128, 16
    dt = jnp.abs(_rand(rng, (g, s, d), scale=0.1))
    x = _rand(rng, (g, s, d), jnp.bfloat16)
    a = -jnp.abs(_rand(rng, (g, d, n)))
    b = _rand(rng, (g, s, n), jnp.bfloat16)
    c = _rand(rng, (g, s, n), jnp.bfloat16)
    y_k, hf_k = ops.selective_scan(dt, x, a, b, c, chunk=32, interpret=True)
    y_r, hf_r = ref.selective_scan_ref(dt, x, a, b, c)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=2e-2, atol=2e-2)


def test_jnp_chunked_scan_matches_kernel_contract(rng):
    """models/ssm.selective_scan_jnp shares the kernel contract exactly."""
    from repro.models.ssm import selective_scan_jnp
    g, s, d, n = 2, 128, 64, 16
    dt = jnp.abs(_rand(rng, (g, s, d), scale=0.1))
    x = _rand(rng, (g, s, d))
    a2 = -jnp.abs(_rand(rng, (d, n)))
    b = _rand(rng, (g, s, n))
    c = _rand(rng, (g, s, n))
    y1, h1 = selective_scan_jnp(dt, x, a2, b, c, chunk=32)
    y2, h2 = ref.selective_scan_ref(dt, x,
                                    jnp.broadcast_to(a2[None], (g, d, n)),
                                    b, c)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=2e-4, atol=2e-4)


# -------------------------------------------------------- flash attention

@pytest.mark.parametrize("window", [0, 100])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
def test_pallas_flash_attention_matches_naive(rng, window, hq, hkv):
    from repro.models import attention as A
    b, s, hd = 2, 256, 128
    q = _rand(rng, (b, s, hq, hd))
    k = _rand(rng, (b, s, hkv, hd))
    v = _rand(rng, (b, s, hkv, hd))
    ref = A.naive_attention(q, k, v, window=window, dtype=jnp.float32)
    out = ops.flash_attention(q, k, v, window=window, interpret=True,
                              q_blk=64, kv_blk=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_pallas_flash_attention_block_invariance(rng):
    b, s, h, hd = 1, 256, 2, 128
    q, k, v = (_rand(rng, (b, s, h, hd)) for _ in range(3))
    base = ops.flash_attention(q, k, v, interpret=True, q_blk=256,
                               kv_blk=256)
    for qb, kb in ((64, 64), (128, 64), (64, 128)):
        out = ops.flash_attention(q, k, v, interpret=True, q_blk=qb,
                                  kv_blk=kb)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   rtol=2e-3, atol=2e-3)
