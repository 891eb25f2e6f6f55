"""Readings behind the limits of ``correct``, on the chip, at a cell's size.

    python bench/calibrate.py --workload <name> --seeds 1,2,... \
        [--steps N] [--control-seeds 7,8,9] [--faults half_batch,...] \
        [--out FILE] [--dump-trace FILE]

In one process: the cell's compiled step is driven from each seed through
its first ``--steps`` steps (default: the cell's ``check_steps``), exactly
as a run does before its window (the program's readings: the lower
readings of each number); then the plain reference replays each seed, and,
on the control seeds, the control (the reference in float8,
``precision="fp8"``) and each planted fault (``refstep.FAULTS``) stand in
the program's place (the upper readings). Every number and every reading
of every seed goes to ``--out`` as JSON, with the largest program reading
and the smallest control and fault readings of each number.

``--dump-trace`` also traces a few steps of the window's loop and writes
the trace in the plain form ``traces.load_plain`` reads (for the tests'
recorded trace), and prints the stats of a few device ops by hand.

The benchmark's runs never run this; it is kept to set and re-check the
limits (``bench/limits/<workload>.json``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc        # noqa: E402
import json      # noqa: E402
import shutil    # noqa: E402
import sys       # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def log(msg: str) -> None:
    print(f"[calibrate {time.perf_counter() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def dump_trace(prog, seed: int, path: str, steps: int = 4) -> None:
    import jax

    from bench import traces
    d = tempfile.mkdtemp(prefix="bench-trace-")
    state = prog.new_state(seed)
    state, _ = prog.first_steps(state, seed, 3)
    jax.profiler.start_trace(d)
    annotate = jax.profiler.TraceAnnotation
    # the window's loop, for a fixed number of steps
    with annotate("bench.window"):
        pending = None
        for k in range(3, 3 + steps):
            with annotate("bench.prep"):
                batch = prog.batch(seed, k)
            with annotate("bench.dispatch"):
                state, mets = prog.compiled(state, batch)
            if pending is not None:
                with annotate("bench.wait"):
                    pending["loss"].block_until_ready()
            pending = mets
        with annotate("bench.wait"):
            pending["loss"].block_until_ready()
    jax.profiler.stop_trace()
    del state, pending, mets
    gc.collect()
    xp = traces.find_xplane(d)
    plain = traces.load_xplane(xp)
    traces.save_plain(plain, path)
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xp)
    for plane in data.planes:
        print(f"plane {plane.name}: lines "
              f"{[ln.name for ln in plane.lines]}", file=sys.stderr)
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                evs = list(line.events)
                print(f"  line {line.name}: {len(evs)} events", file=sys.stderr)
                seen = set()
                for e in evs:
                    key = e.name.split(".")[0]
                    if key in seen or len(seen) > 25:
                        continue
                    seen.add(key)
                    print(f"    {e.name!r} {e.duration_ns:.0f} ns "
                          f"{dict(e.stats)}"[:600], file=sys.stderr)
    s = traces.summarize(plain)
    if s is not None:
        print(f"summary: busy {s.busy_s:.4f} of {s.window_s:.4f} s; ops "
              f"{s.device_ops}; gaps {s.idle_gaps}", file=sys.stderr)
    shutil.rmtree(d, ignore_errors=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--dump-trace", default="")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    faults = [f for f in args.faults.split(",") if f]

    sys.argv = [sys.argv[0]]
    from bench import run as R
    devices = R.find_chips(R._chips_of(args.workload))
    import jax

    from repro.launch.cache import init_compile_cache
    log(f"compile cache: {init_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import compare, refstep, spec
    from bench.harness import Program

    cell = spec.load_cell(args.workload)
    steps = args.steps or int(cell.limits["check_steps"])
    prog = Program(cell, devices)
    prog_readings = {}
    for seed in seeds:
        state = prog.new_state(seed)
        if prog.compiled is None:
            prog.compile(state, prog.batch(seed, 0))
            log("step compiled")
        state, prog_readings[seed] = prog.first_steps(state, seed, steps)
        del state
        gc.collect()
        r = prog_readings[seed]
        log(f"program seed {seed}: losses {r.losses} masks {r.masks} "
            f"lhs {r.lhs} rhs {r.rhs}")
    if args.dump_trace:
        dump_trace(prog, seeds[0], args.dump_trace)
    del prog
    gc.collect()

    model = spec.reference_model(cell)
    out = {"workload": args.workload, "device": devices[0].device_kind,
           "steps": steps, "program": {}, "control": {},
           "faults": {f: {} for f in faults}, "readings": {}}

    def keep(seed, who, readings):
        out["readings"].setdefault(seed, {})[who] = dataclasses.asdict(
            readings)

    def judge(seed, who, readings, sound=None):
        """The numbers of ``readings`` against the reference that followed
        its decisions (``sound`` when it followed the same ones)."""
        if sound is None or sound.masks != readings.masks:
            sound = refstep.reference_run(model, cell.config, cell.traffic,
                                          seed, steps, follow=readings.masks)
        keep(seed, who, readings)
        keep(seed, f"reference for {who}", sound)
        nums = compare.numbers(readings, sound)
        log(f"seed {seed} {who} vs reference: {nums}")
        return nums, sound

    for seed in seeds:
        out["program"][seed], ref = judge(seed, "program",
                                          prog_readings[seed])
        if seed in ctl_seeds:
            ctl = refstep.reference_run(model, cell.config, cell.traffic,
                                        seed, steps, precision="fp8")
            out["control"][seed], _ = judge(seed, "control", ctl, ref)
            for f in faults:
                flt = refstep.reference_run(model, cell.config, cell.traffic,
                                            seed, steps, fault=f)
                out["faults"][f][seed], _ = judge(seed, f, flt, ref)
        del ref
        gc.collect()

    def agg(rows, fn):
        names = set().union(*[r.keys() for r in rows.values()]) if rows else ()
        return {n: fn(r[n] for r in rows.values() if n in r) for n in names}

    out["lower"] = agg(out["program"], max)
    out["control_min"] = agg(out["control"], min)
    out["fault_min"] = {f: agg(v, min) for f, v in out["faults"].items()}
    text = json.dumps(out, indent=1, default=float)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(json.dumps({"lower": out["lower"], "control_min": out["control_min"],
                      "fault_min": out["fault_min"]}, default=float))


if __name__ == "__main__":
    main()
