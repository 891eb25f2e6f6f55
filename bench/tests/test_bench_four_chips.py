"""CPU test of a four-chip cell's run, on four host devices.

The tiny configuration of ``test_bench_correct`` runs as the four-chip cell
``tiny.cada2-dp4`` (one worker per device on the ``data`` axis, the flat
state ZeRO'd over it: ``harness.Program``'s mesh path) through the whole of
a run after the look for a chip (``run.run``), in a child process whose host
platform has four devices: this process's JAX has already fixed its own
device count. The child runs the sound cell, the one-device tiny cell on
the same seed, and the four-chip cell with its timed path broken underneath
in each way a four-chip training cell can break (``FAULTS``); it prints one
JSON object, which the tests read.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from test_bench_correct import _half_batch, _state_unchanged, write_tiny_root

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SEED = 2 ** 31 + 21
CELL = "tiny.cada2-dp4"
# The sharded step sums eq. (3)'s rows as a tree across devices where the
# one-device step adds them in a fixed order, so the two part by float32
# rounding in ∇̄ from step 1 on; Adam's first steps scale that up. On this
# seed the first three losses agree to 1e-6; 1e-4 leaves that room and is
# 40 times under the tiny cell's loss_gap limit (4e-3), which a step that
# loses rows or an exchange exceeds.
LOSS_ATOL = 1e-4
FAULTS = ("state_unchanged", "half_batch", "no_exchange")


# --------------------------------------------------------------- the child

def _capture(store):
    """A program hook that keeps the readings of the first steps."""
    def hook(prog):
        real = prog.first_steps

        def first_steps(state, seed, steps):
            state, readings = real(state, seed, steps)
            store.append(readings.losses)
            return state, readings
        prog.first_steps = first_steps
    return hook


def _no_exchange(prog):
    """eq. (3)'s sum with the exchange between chips left out: each device
    sums only the worker row it holds into its block of ∇̄."""
    import jax.numpy as jnp

    from repro.kernels import ops
    real = ops.eq3_row_mean

    def local_rows(plane, m_total, base=None, *, shard=None, interpret=None):
        if shard is None:
            return real(plane, m_total, base, interpret=interpret)
        rows, n = plane.shape
        width = n // rows
        mean = jnp.concatenate(
            [plane[s, s * width:(s + 1) * width] for s in range(rows)]
        ).astype(jnp.float32) / m_total
        return mean if base is None else base + mean
    ops.eq3_row_mean = local_rows


def child(root: Path) -> dict:
    import jax

    from bench import run as R
    from bench import spec
    from repro.kernels import ops

    devices = jax.devices()
    assert len(devices) == 4, devices

    def run(workload, hook=None, trace=0):
        args = R.parse_args(["--workload", workload, "--seed", str(SEED),
                             "--seconds", "0.5", "--trace", str(trace)])
        chips = spec.load_cell(workload, root=root).chips
        res = R.run(args, devices[:chips], root=root, program_hook=hook)
        return {"correct": res["correct"], "checks": res["checks"],
                "count": res["device"]["count"]}

    out, losses = {}, []
    out["sound"] = run(CELL, _capture(losses), trace=1)
    out["one_device"] = run("tiny.cada2", _capture(losses))
    out["losses"] = losses
    real = ops.eq3_row_mean
    plants = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
              "no_exchange": _no_exchange}
    for name in FAULTS:
        try:
            out[name] = run(CELL, plants[name])
        finally:
            ops.eq3_row_mean = real
    return out


# --------------------------------------------------------------- the tests

@pytest.fixture(scope="module")
def four(tmp_path_factory):
    root = write_tiny_root(tmp_path_factory.mktemp("tiny4"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join(
               [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")])}
    proc = subprocess.run([sys.executable, __file__, str(root)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_cell_is_correct(four):
    assert four["sound"]["count"] == 4
    assert four["sound"]["correct"], four["sound"]["checks"]


def test_sharded_losses_match_one_device(four):
    sharded, one = four["losses"]
    assert len(sharded) == len(one)
    for a, b in zip(sharded[:3], one[:3]):
        assert a == pytest.approx(b, abs=LOSS_ATOL), (sharded, one)


@pytest.mark.parametrize("fault", FAULTS)
def test_broken_sharded_step_is_not_correct(four, fault):
    assert not four[fault]["correct"], four[fault]["checks"]


if __name__ == "__main__":
    print(json.dumps(child(Path(sys.argv[1]))), flush=True)
