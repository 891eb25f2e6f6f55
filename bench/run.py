"""One run of one benchmark cell on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the chips (a TPU, as many as the cell asks for, or it exits non-zero
and prints no result), turns on the persistent compile cache, builds the
cell from its files, makes the trainer state from the seed on the device,
compiles the cell's step, drives it through its first steps (read for
``correct``; the cell's limit file gives how many), then measures for
``--seconds`` with one step always in flight. With ``--trace 1`` the window
runs under the profiler and the run reports the cell's per-layer metrics;
with ``--trace 0`` its end-to-end metrics. After the window the program's
state is freed and the plain reference replays the first steps, taking the
program's upload decisions and judging each by its own gate; the comparison
decides ``correct``. The last line of stdout is one JSON object.
"""
import time

T_START = time.perf_counter()   # set-up counts from the start of the process

import argparse      # noqa: E402
import contextlib    # noqa: E402
import gc            # noqa: E402
import json          # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402
import tempfile      # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

EXIT_NO_CHIP = 3


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def find_chips(n: int):
    """The first ``n`` TPU devices, or exit non-zero with no result."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        log(f"JAX found no devices: {e}")
        sys.exit(EXIT_NO_CHIP)
    if devs[0].platform != "tpu":
        log(f"needs a TPU; JAX found platform {devs[0].platform!r}")
        sys.exit(EXIT_NO_CHIP)
    if len(devs) < n:
        log(f"the cell needs {n} chips; JAX found {len(devs)}")
        sys.exit(EXIT_NO_CHIP)
    return devs[:n]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def percentile(xs, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(xs, dtype=np.float64), q))


def run(args, devices, *, root: Path = ROOT, bench_dir: Path | None = None,
        program_hook=None) -> dict:
    """Everything after the look for a chip: returns the result object.
    ``program_hook(program)`` may replace parts of the built program (the
    fault tests use it)."""
    import jax
    import numpy as np

    from bench import compare, refstep, spec, traces
    from bench.harness import Program

    cell = spec.load_cell(args.workload, root=root, bench_dir=bench_dir)
    check_steps = int(cell.limits["check_steps"])
    peaks = spec.peaks(devices[0].device_kind) if devices[0].platform == \
        "tpu" else None
    prog = Program(cell, devices)
    if program_hook is not None:
        program_hook(prog)
    annotate = (jax.profiler.TraceAnnotation if args.trace
                else (lambda _name: contextlib.nullcontext()))
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    state = prog.new_state(args.seed)
    t = time.perf_counter()
    prog.compile(state, prog.batch(args.seed, 0))
    log(f"step compiled or loaded in {time.perf_counter() - t:.2f} s")
    state, readings = prog.first_steps(state, args.seed, check_steps)
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    state, win = prog.window(state, args.seed, check_steps, args.seconds,
                             annotate)
    if trace_dir:
        jax.profiler.stop_trace()
    setup_s = win.t0 - T_START
    losses = np.asarray(jax.device_get(win.losses))
    gate = {k: np.asarray(jax.device_get(v)) for k, v in win.gate.items()}
    masks = gate["upload_mask"]
    mem = [d.memory_stats() or {} for d in devices]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in mem)
    memory = prog.memory_analysis()
    shards = prog.shards
    del state, win.losses, win.gate, prog
    gc.collect()

    done = win.done
    steps = len(done)
    window_s = done[-1] - win.t0
    tokens_per_s = steps * cell.tokens_per_step / window_s
    step_s = np.diff(np.asarray(done))
    failed = int(np.sum(~np.isfinite(losses)))
    log(f"window: {steps} steps in {window_s:.3f} s, set-up {setup_s:.3f} s, "
        f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    for i in np.argsort(step_s)[::-1][:3]:
        prep, dispatch, wait = win.phases[i + 1]
        log(f"interval {i}: {step_s[i]:.4f} s (prep {prep:.4f}, dispatch "
            f"{dispatch:.4f}, wait {wait:.4f})")

    summary, plain = None, None
    if trace_dir:
        plain = traces.load_xplane(traces.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        summary = traces.summarize(plain)

    t = time.perf_counter()
    ref = refstep.reference_run(spec.reference_model(cell), cell.config,
                                cell.traffic, args.seed, check_steps,
                                follow=readings.masks)
    log(f"reference: {time.perf_counter() - t:.2f} s")
    nums = compare.numbers(readings, ref)
    if readings.lhs is not None:
        nums.update(compare.audit(
            cell.traffic["rule"], readings.masks + masks.tolist(),
            readings.lhs + gate["lhs"].tolist(),
            readings.rhs + gate["rhs"].tolist(),
            readings.dtheta_sq + gate["dtheta_sq"].tolist()))
    nums["window_nonfinite"] = float(failed)
    limits = {**cell.limits, "limits": {**cell.limits.get("limits", {}),
                                        "window_nonfinite": 0.0}}
    correct, rows = compare.verdict(nums, limits)

    if args.trace:
        view = SimpleNamespace(
            cell=cell, cfg=cell.config, traffic=cell.traffic,
            chips=cell.chips, peaks=peaks, trace=plain, summary=summary,
            steps=steps, window_s=window_s, tokens_per_s=tokens_per_s,
            memory=memory, masks=masks, state_shards=shards)
        metrics = {}
        for m in cell.per_layer:
            value = spec.metric_reader(cell, m["name"])(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"tokens_per_s": tokens_per_s,
               "step_s_p90": percentile(step_s, 90),
               "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": steps,
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {name: {"value": value, "limit": bound}
                        for name, value, bound in rows}
    return result


def main() -> None:
    args = parse_args()
    devices = find_chips(_chips_of(args.workload))
    from repro.launch.cache import init_compile_cache
    import jax
    log(f"compile cache: {init_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run(args, devices)
    for name, chk in result["checks"].items():
        print(f"check {name}: {chk['value']!r} limit {chk['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def _chips_of(workload: str) -> int:
    from bench import spec
    return spec.load_cell(workload).chips


if __name__ == "__main__":
    main()
