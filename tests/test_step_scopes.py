"""The phases of the trainer step carry their names into the compiled
program: every ``cada.*`` scope the step writes is in the compiled text,
nearly every compute instruction maps to a phase (the map the chip
benchmark reads a trace with, ``bench/scopes.py``), and the persistent
compile cache keys on those names."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.core.rules import CommRule
from repro.distributed.trainer import (TrainHParams, init_train_state,
                                       make_train_step, worker_split)

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import scopes, traces  # noqa: E402

PHASES = {
    "cada2": ("cada.grad_eval", "cada.pack", "cada.rule_state", "cada.gate",
              "cada.eq3", "cada.server_update"),
    "always": ("cada.grad_eval", "cada.pack", "cada.eq3",
               "cada.server_update"),
}
COMPUTE = ("fusion", "custom-call", "dot")


CACHE_FLAGS = ("jax_compilation_cache_include_metadata_in_key",
               "jax_traceback_in_locations_limit",
               "jax_hlo_source_file_canonicalization_regex")


@pytest.fixture(scope="module")
def entry_settings():
    """The JAX settings every entry point compiles under
    (``init_compile_cache``), restored afterwards; no cache directory."""
    from repro.launch.cache import init_compile_cache
    saved = {f: getattr(jax.config, f) for f in CACHE_FLAGS}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_COMPILATION_CACHE_DIR", "unused")
        init_compile_cache()
    yield
    for f, v in saved.items():
        jax.config.update(f, v)


@pytest.fixture(scope="module", params=sorted(PHASES))
def compiled_step(request, entry_settings):
    kind = request.param
    cfg = C.get_smoke_config("stablelm-1.6b")
    hp = TrainHParams(rule=CommRule(kind=kind, c=1.0, d_max=10,
                                    max_delay=50), lr=1e-4)
    m = 2
    state = jax.eval_shape(
        lambda: init_train_state(cfg, hp, m, jax.random.PRNGKey(0)))
    batch = worker_split({"tokens": jnp.zeros((4, 33), jnp.int32)}, m)
    step = jax.jit(make_train_step(cfg, hp, m), donate_argnums=(0,))
    return kind, step.lower(state, batch).compile().as_text()


def test_every_phase_is_in_the_compiled_step(compiled_step):
    kind, text = compiled_step
    for phase in PHASES[kind]:
        assert f"/{phase}" in text or f"({phase}" in text, phase
    named = {s for s in scopes.op_scopes(text).values() if s}
    assert named == set(PHASES[kind])


def test_compute_instructions_map_to_a_phase(compiled_step):
    kind, text = compiled_step
    op_map = scopes.op_scopes(text)
    compute = []
    for line in text.splitlines():
        line = line.strip()
        name, op = traces.split_instruction(line.removeprefix("ROOT "))
        if op in COMPUTE:
            compute.append(name)
    mapped = sum(op_map[n] is not None for n in compute)
    assert len(compute) > 100
    assert mapped >= 0.9 * len(compute), (kind, mapped, len(compute))


def _key(lowered):
    from jax._src import cache_key, compiler
    return cache_key.get(lowered._lowering.stablehlo(),
                         np.array(jax.devices()[:1]),
                         compiler.get_compile_options(1, 1),
                         jax.extend.backend.get_backend())


def _scoped(name):
    def f(x):
        with jax.named_scope(name):
            return jnp.sin(x) * 2.0
    return jax.jit(f)


def _lower_here(fn):
    return fn.lower(jnp.ones(8))


def _lower_elsewhere(fn):
    x = jnp.ones(8)
    return fn.lower(x)


def test_compile_cache_keys_on_the_phase_names(entry_settings):
    """Under ``init_compile_cache``'s settings an executable compiled from
    code with other scope names is never served, while the call site
    that compiles the step does not change the key."""
    eq3 = _key(_lower_here(_scoped("cada.eq3")))
    assert eq3 == _key(_lower_elsewhere(_scoped("cada.eq3")))
    assert eq3 != _key(_lower_here(_scoped("cada.gate")))
