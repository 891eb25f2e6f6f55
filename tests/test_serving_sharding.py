"""Sharding policy + serving builders on the host mesh, and one real
(subprocess) dry-run combo as an integration test."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import repro.configs as C
from repro.distributed.serving import jit_decode_step, jit_prefill_step
from repro.distributed.sharding import (batch_pspecs, cache_pspecs,
                                        param_pspecs, wants_fsdp)
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.model import abstract_params, init_cache, init_params


def test_param_pspecs_cover_every_leaf():
    """Every arch's parameter tree gets a spec of matching rank."""
    mesh = make_host_mesh()
    for arch in C.list_archs():
        cfg = C.get_config(arch)
        aps = abstract_params(cfg)
        specs = param_pspecs(cfg, mesh)
        for leaf, spec in zip(jax.tree.leaves(aps), jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, P))):
            assert len(spec) <= leaf.ndim, (arch, leaf.shape, spec)


def test_fsdp_threshold_picks_big_archs():
    mesh = make_host_mesh()
    assert wants_fsdp(C.get_config("llama3-405b"), mesh)
    assert not wants_fsdp(C.get_config("internlm2-1.8b"), mesh)


def test_fsdp_axes_extension():
    """("data","pod") FSDP composes for the 405B multi-pod policy."""
    # host mesh has no pod axis: the pod entry must drop out gracefully
    mesh = make_host_mesh()
    cfg = C.get_config("llama3-405b")
    specs = param_pspecs(cfg, mesh, fsdp=True, fsdp_axes=("data", "pod"))
    flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert all(isinstance(s, P) for s in flat)


def test_batch_pspecs_divisibility_guard():
    mesh = make_host_mesh()
    sds = {"tokens": jax.ShapeDtypeStruct((1,), jnp.int32)}  # B=1
    spec = batch_pspecs(sds, mesh)["tokens"]
    # B=1 cannot shard over a >1 data axis
    if mesh.shape["data"] > 1:
        assert spec[0] is None


def test_cache_pspecs_shapes():
    mesh = make_host_mesh()
    cfg = C.get_smoke_config("zamba2-2.7b")
    cache = jax.eval_shape(lambda: init_cache(cfg, 4, 64))
    specs = cache_pspecs(cfg, cache, mesh)
    assert len(specs.k) == 5 and len(specs.ssm) == 5


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "falcon-mamba-7b"])
def test_decode_step_builder_runs(arch):
    cfg = C.get_smoke_config(arch)
    mesh = make_host_mesh()
    with jax.set_mesh(mesh):
        params = init_params(cfg, jax.random.PRNGKey(0))
        step, cache_sds, inputs_sds = jit_decode_step(cfg, mesh, 2, 16)
        cache = init_cache(cfg, 2, 16)
        logits, cache = step(params, cache,
                             {"tokens": jnp.ones((2,), jnp.int32)})
    assert logits.shape == (2, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


def test_prefill_step_builder_runs():
    cfg = C.get_smoke_config("granite-moe-1b-a400m")
    mesh = make_host_mesh()
    with jax.set_mesh(mesh):
        params = init_params(cfg, jax.random.PRNGKey(0))
        inputs = {"tokens": jnp.ones((2, 16), jnp.int32)}
        sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), inputs)
        step = jit_prefill_step(cfg, mesh, sds)
        logits, cache = step(params, inputs)
    assert logits.shape == (2, cfg.vocab)
    assert int(cache.index) == 16


def test_production_mesh_requires_512_devices():
    """On the 1-device test process the production mesh must refuse —
    proving tests don't silently fake the fleet (the dry-run does that,
    explicitly, via XLA_FLAGS)."""
    with pytest.raises(Exception):
        make_production_mesh()


@pytest.mark.slow
def test_dryrun_one_combo_subprocess():
    """Integration: a real dry-run combo (lower+compile on 512 fake
    devices) in a fresh interpreter."""
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "granite-moe-1b-a400m", "--shape", "decode_32k"],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
        cwd="/root/repo")
    assert "all 1 combos passed" in res.stdout, res.stdout + res.stderr
