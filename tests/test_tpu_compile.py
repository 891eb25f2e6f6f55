"""Compile the main path's Pallas kernels for a described TPU v5e at real
widths — no chip needed: the TPU compiler is installed here, and it refuses
what interpret mode cannot see (scalar stores to VMEM, block shapes the
tiling rejects, unaligned slices). Each test asserts the kernel is in the
compiled program as a ``tpu_custom_call``.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and it keeps
it until it exits, so every test that needs it lives in this one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.stablelm_1_6b import chip_config
from repro.distributed.trainer import flat_layout
from repro.kernels import ops

M = 4   # workers of the one-chip cada2 cut


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: an entry written for a described chip cannot be read back
    without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@pytest.fixture(scope="module")
def n_flat():
    """Flat width of the one-chip stablelm-1.6b cut (about 1.5e8)."""
    return flat_layout(chip_config()).n_flat


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("moments", [jnp.float32, jnp.bfloat16])
def test_fused_amsgrad_flat_compiles(one_chip, n_flat, moments):
    theta, grad = (_sds(one_chip, (n_flat,)) for _ in range(2))
    h, vhat = (_sds(one_chip, (n_flat,), moments) for _ in range(2))
    txt = _compiled_text(
        lambda t, hh, vh, g: ops.fused_amsgrad_flat(t, hh, vh, g, 3e-4,
                                                    interpret=False),
        theta, h, vhat, grad)
    assert "tpu_custom_call" in txt


def test_diff_sq_norm_flat_compiles(one_chip, n_flat):
    a = _sds(one_chip, (n_flat,))
    txt = _compiled_text(
        lambda x, y: ops.diff_sq_norm_flat(x, y, interpret=False), a, a)
    assert "tpu_custom_call" in txt


def test_batched_diff_sq_norm_flat_compiles(one_chip, n_flat):
    a = _sds(one_chip, (M, n_flat))
    txt = _compiled_text(
        lambda x, y: ops.batched_diff_sq_norm(x, y, interpret=False), a, a)
    assert "tpu_custom_call" in txt


def test_batched_sq_norm_flat_compiles(one_chip, n_flat):
    a = _sds(one_chip, (M, n_flat))
    txt = _compiled_text(
        lambda x: ops.batched_sq_norm(x, interpret=False), a)
    assert "tpu_custom_call" in txt


def test_eq3_row_mean_compiles_to_one_pass(one_chip, n_flat):
    """Eq. (3)'s order-fixed row sum over the M=4 wire plane, into ∇̄,
    compiles to the one-pass kernel: no loop, and no row copied out by a
    dynamic slice."""
    plane = _sds(one_chip, (M, n_flat))
    nabla = _sds(one_chip, (n_flat,))
    txt = _compiled_text(
        lambda w, b: ops.eq3_row_mean(w, M, b, interpret=False), plane, nabla)
    assert "tpu_custom_call" in txt
    assert "while" not in txt
    assert "dynamic-slice" not in txt


def test_flash_attention_kernel_compiles(one_chip):
    """stablelm-1.6b's heads (32 × 64) at a 2,048-token sequence."""
    cfg = chip_config()
    q = _sds(one_chip, (1, 2048, cfg.n_heads, cfg.hd), jnp.bfloat16)
    txt = _compiled_text(
        lambda a, b, c: ops.flash_attention(a, b, c, interpret=False),
        q, q, q)
    assert "tpu_custom_call" in txt


@pytest.mark.xfail(strict=True, reason=(
    "Mosaic: Slice shape along dimension 2 must be aligned to tiling (128), "
    "but is 16 — the kernel slices the N=16 state axis; models/ssm.py "
    "trains through the jnp scan instead"))
def test_selective_scan_compiles(one_chip):
    """falcon-mamba-7b's mamba1 block: d_inner 8192, state N=16."""
    g, s, d, n = 1, 1024, 8192, 16
    dt = _sds(one_chip, (g, s, d))
    a = _sds(one_chip, (g, d, n))
    b = _sds(one_chip, (g, s, n))
    txt = _compiled_text(
        lambda dt_, x, a_, b_, c: ops.selective_scan(dt_, x, a_, b_, c,
                                                     interpret=False),
        dt, dt, a, b, b)
    assert "tpu_custom_call" in txt
