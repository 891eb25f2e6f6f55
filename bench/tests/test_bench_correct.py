"""CPU tests of what decides ``correct``, at a size a test run can hold.

A tiny dense configuration (d 64, 2 layers, vocab 256, seq 32, M=4) runs
through the whole of a run after the look for a chip (``run.run``): the
program's compiled step, its first ``TINY_STEPS`` steps, the window, the
plain reference and the comparison. The sound program comes out correct;
with the timed path broken underneath it does not: a step that returns its
state unchanged, half of every worker's rows left out, a gate that never
skips (c = 0) and an RHS ten times too large. The reference's own planted
faults (``refstep.FAULTS``, a stale-iterate ring read at θ^{k-1} among
them) and the control, the reference in float8, fail one of the numbers
too. The tiny cada2 cell's gate skips from its fifth step on, so the
compared steps hold skips and reads of the ring at τ >= 2. The limits
here are the tiny cell's own, set from its CPU readings
(``TINY_LIMITS``); the chip cells' limits are in ``bench/limits``.
"""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

jax = pytest.importorskip("jax")
jnp = jax.numpy

from bench import compare, refstep, spec  # noqa: E402
from bench import run as R                 # noqa: E402

TINY_STEPS = 12
# sound, 8 seeds, at most: loss 1.4e-3, grad 9.4e-4, update 3.0e-3, LHS
# 4.9e-3, RHS 2.9e-2, gate 3.8e-2; control, 3 seeds, at least: loss 1.3e-2,
# grad 1.3e-2, LHS 4.2e-2, gate 0.74; the gate faults: RHS 1.0, gate 0.59
TINY_LIMITS = {"loss_gap": 4e-3, "grad_gap": 4e-3, "update_gap": 6e-3,
               "lhs_gap": 2e-2, "rhs_gap": 0.1, "gate_gap": 0.15,
               "forced_skips": 0.0, "gate_audit": 0.0, "rhs_audit": 1e-4}


# the tiny cells: name -> the traffic mix each takes (at seq 32, lr 1e-3)
TINY_CELLS = {"tiny.cada2": "cada2-m4", "tiny.always": "always-m4",
              "tiny.cada2-dp4": "cada2-dp4"}


def write_tiny_root(root):
    """A checkout under ``root`` whose cells are the tiny configuration
    under each mix of ``TINY_CELLS``, with the tiny limits."""
    bench = root / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__",
                                                  "limits"))
    (bench / "limits").mkdir()
    cfg = json.loads((bench / "configs" / "stablelm-2-1.6b-chip.json")
                     .read_text())
    cfg.update(name="tiny", d_model=64, vocab=256, n_heads=4, n_kv_heads=2,
               d_ff=128, rotary_pct=0.5)
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                     "file": "bench/configs/tiny.json", "why": "test"}]
    b["workloads"] = []
    for name, mix in TINY_CELLS.items():
        t = json.loads((bench / "traffic" / f"{mix}.json").read_text())
        t.update(seq=32, lr=1e-3)
        (bench / "traffic" / f"tiny-{mix}.json").write_text(json.dumps(t))
        b["workloads"].append({"name": name, "config": "tiny",
                               "traffic": f"tiny-{mix}", "chips": t["chips"],
                               "why": "test"})
        (bench / "limits" / f"{name}.json").write_text(
            json.dumps({"check_steps": TINY_STEPS, "limits": TINY_LIMITS}))
    for m in b["per_layer"]:
        m["workloads"] = list(TINY_CELLS)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return write_tiny_root(tmp_path_factory.mktemp("tiny"))


def _run(root, workload, seed=2 ** 31 + 3, trace=0, hook=None):
    args = R.parse_args(["--workload", workload, "--seed", str(seed),
                         "--seconds", "0.5", "--trace", str(trace)])
    return R.run(args, jax.devices()[:1], root=root, program_hook=hook)


def _state_unchanged(prog):
    """A step that returns the state it was given."""
    real = prog.jitted
    prog.jitted = jax.jit(lambda s, b: (s, real(jax.tree.map(jnp.copy, s),
                                                 b)[1]))


def _half_batch(prog):
    """Half of every worker's rows left out, the mean taken over the rest."""
    real = prog.jitted
    prog.jitted = jax.jit(
        lambda s, b: real(s, {"tokens": b["tokens"][:, : b["tokens"].shape[1]
                                                     // 2]}),
        donate_argnums=(0,))


def _gate_with_c(scale):
    def plant(prog):
        import dataclasses
        from repro.distributed.trainer import make_train_step
        rule = dataclasses.replace(prog.hp.rule, c=prog.hp.rule.c * scale)
        hp = dataclasses.replace(prog.hp, rule=rule)
        prog.jitted = jax.jit(make_train_step(prog.cfg, hp, prog.m),
                              donate_argnums=(0,))
    return plant


@pytest.mark.parametrize("kind", ["cada2", "always"])
def test_sound_program_is_correct(tiny_root, kind):
    res = _run(tiny_root, f"tiny.{kind}", trace=1)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    if kind == "cada2":
        assert "upload_pct" in res["metrics"]
        assert res["checks"]["lhs_gap"]["limit"] is not None


@pytest.mark.parametrize(
    "fault", [_state_unchanged, _half_batch, _gate_with_c(0.0),
              _gate_with_c(10.0)],
    ids=["state_unchanged", "half_batch", "gate_never_skips", "rhs_scaled"])
def test_broken_step_is_not_correct(tiny_root, fault):
    res = _run(tiny_root, "tiny.cada2", hook=fault)
    assert not res["correct"], res["checks"]


def test_the_gate_skips_within_the_compared_steps(tiny_root):
    """The compared steps hold skips, and a read of the ring at τ >= 2."""
    cell = spec.load_cell("tiny.cada2", root=tiny_root)
    ref = refstep.reference_run(spec.reference_model(cell), cell.config,
                                cell.traffic, 2 ** 31 + 3, TINY_STEPS)
    skipped = [k for k, mask in enumerate(ref.masks) if not all(mask)]
    assert skipped and skipped[0] < TINY_STEPS - 1, ref.masks


def test_reference_weights_are_the_seed_model(tiny_root):
    """One seed means one model: the reference's weights equal the
    program's initialization, leaf for leaf."""
    from bench.harness import program_config
    from repro.models.model import init_params
    cell = spec.load_cell("tiny.cada2", root=tiny_root)
    key = refstep.seed_key(2 ** 31 + 11)
    ref = spec.reference_model(cell).init_weights(cell.config, key)
    prog = init_params(program_config(cell.config), key)
    assert jax.tree.structure(ref) == jax.tree.structure(prog)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(prog)):
        assert a.dtype == b.dtype and bool(jnp.array_equal(a, b))


def test_reference_loss_matches_the_program_in_float32(tiny_root):
    from bench.harness import program_config
    from repro.models.model import init_params, lm_loss
    cell = spec.load_cell("tiny.cada2", root=tiny_root)
    cfg32 = {**cell.config, "dtype": "float32", "remat": False}
    key = refstep.seed_key(5)
    params = init_params(program_config(cfg32), key)
    toks = jnp.asarray(refstep.feed.step_tokens(cell.traffic, 256, 5, 0))
    with jax.default_matmul_precision("highest"):
        want = float(lm_loss(program_config(cfg32), params,
                             {"tokens": toks})[0])
    got = float(spec.reference_model(cell).loss(params, toks, cfg32))
    assert got == pytest.approx(want, abs=2e-5)


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 9])
def test_control_fails_a_number(tiny_root, seed):
    cell = spec.load_cell("tiny.cada2", root=tiny_root)
    model = spec.reference_model(cell)
    ctl = refstep.reference_run(model, cell.config, cell.traffic, seed,
                                TINY_STEPS, precision="fp8")
    ref = refstep.reference_run(model, cell.config, cell.traffic, seed,
                                TINY_STEPS, follow=ctl.masks)
    ok, rows = compare.verdict(compare.numbers(ctl, ref),
                               {"limits": TINY_LIMITS})
    assert not ok, rows


@pytest.mark.parametrize("fault", refstep.FAULTS)
def test_reference_faults_fail_a_number(tiny_root, fault):
    cell = spec.load_cell("tiny.cada2", root=tiny_root)
    model = spec.reference_model(cell)
    seed = 2 ** 31 + 3
    bad = refstep.reference_run(model, cell.config, cell.traffic, seed,
                                TINY_STEPS, fault=fault)
    ref = refstep.reference_run(model, cell.config, cell.traffic, seed,
                                TINY_STEPS, follow=bad.masks)
    ok, rows = compare.verdict(compare.numbers(bad, ref),
                               {"limits": TINY_LIMITS})
    assert not ok, rows
