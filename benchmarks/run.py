"""Benchmark aggregator: one entry per paper table/figure + the beyond-paper
benches. Prints a CSV summary and writes per-bench JSON under results/.

  python -m benchmarks.run            # fast settings (CI-sized)
  python -m benchmarks.run --full     # paper-sized iteration counts
  python -m benchmarks.run --only cada   # just the BENCH_cada.json tracker

Every run also refreshes ``BENCH_cada.json`` (steps/sec of the jitted
engine + uploads saved by CADA2 vs distributed Adam on the logreg problem)
so the perf trajectory is tracked across PRs.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

BENCH_PATH = "BENCH_cada.json"
SIM_BENCH_PATH = "BENCH_sim.json"
HIER_BENCH_PATH = "BENCH_hierarchical.json"


def _load_baseline() -> dict | None:
    try:
        with open(BENCH_PATH) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def _warn_if_regressed(name: str, new_sps: float, old: dict | None) -> None:
    """Warn (stderr) when steps/sec drops >10% vs the committed baseline."""
    if not old:
        return
    old_sps = old.get("steps_per_sec")
    if old_sps and new_sps < 0.9 * old_sps:
        print(f"[cada] WARNING: {name} steps/sec regressed "
              f"{old_sps} -> {new_sps} (>{10}% below the committed "
              f"baseline in {BENCH_PATH})", file=sys.stderr)


def _comm_state_bytes(comm) -> tuple[int, int]:
    """(total comm-state bytes, eval-point-extras bytes) of an engine's
    flat comm state — the ring-vs-dense memory story per arm."""
    import jax
    if comm is None:
        return 0, 0
    total = sum(int(l.size * l.dtype.itemsize)
                for l in jax.tree.leaves(comm))
    extras = sum(int(l.size * l.dtype.itemsize)
                 for l in jax.tree.leaves(comm.extras))
    return total, extras


def _second_eval_frac(eng, st, batches, step_s: float) -> float:
    """Fraction of a measured engine step spent in the rule's SECOND
    gradient evaluation: (jitted two-point eval − jitted fresh-only eval)
    per call, over the arm's measured seconds per step. 0.0 for
    single-eval rules."""
    import jax

    from repro.core import flat as F

    if eng.strategy.grad_evals_per_iter < 2 or step_s <= 0:
        return 0.0
    b0 = jax.tree.map(lambda x: x[0], batches)
    layout, extras = eng._layout, st.comm.extras
    f2 = jax.jit(lambda p, b: F.eval_two_point(
        eng.strategy, layout, extras, p, b, eng.m, vgrad=eng._vgrad,
        vgrad_per=eng._vgrad_per, fuse_evals=eng._fuse_evals,
        group_evals=eng._group_evals))
    f1 = jax.jit(lambda p, b: eng._vgrad(p, b))
    ts = {}
    for name, f in (("two", f2), ("one", f1)):
        jax.block_until_ready(f(st.params, b0))
        best = float("inf")
        for _ in range(5):
            t0 = time.time()
            for _ in range(50):
                out = f(st.params, b0)
            jax.block_until_ready(out)
            best = min(best, (time.time() - t0) / 50)
        ts[name] = best
    return round(min(1.0, max(0.0, ts["two"] - ts["one"]) / step_s), 4)


def bench_cada(iters: int = 300, lm_steps: int = 30) -> dict:
    """Headline perf numbers, tracked across PRs in ``BENCH_cada.json``:

      * engine throughput + communication saved, logreg-CADA2 vs always
        (distributed Adam), matched hyper-parameters, on the fused
        flat-plane hot path with donated state buffers. The cada2 arm
        runs the DEFAULT eval dispatch (stale-iterate ring + stacked
        ``fuse_evals`` two-point eval); ``cada2_unfused`` pins the
        two-call dispatch so the stacked win stays measured;
      * ``gating_overhead_frac`` = 1 − cada2/always steps/sec — what the
        adaptive rule COSTS per iteration (its savings are the uploads);
      * per arm: ``second_eval_frac`` (measured share of a step spent in
        the second gradient evaluation) and worker-state bytes (total
        comm state + the eval-point extras — the ring-vs-dense story);
      * an interleaved M-sweep micro-arm (M=10/256/2048) showing the
        ring's memory and steps/sec scaling (``m_sweep``);
      * trainer steps/sec on the LM path (ROADMAP's named next metric).

    Warns on stderr when any steps/sec regresses >10% vs the committed
    baseline or when the donated state fails to alias in the compiled
    module (a "donation" that silently copies); the alias count is also
    recorded per arm in the JSON.
    """
    import jax
    import numpy as np

    from repro.core.engine import CADAEngine, make_sampler
    from repro.core.rules import CommRule
    from repro.data.partition import pad_to_matrix, uniform_partition
    from repro.data.synthetic import ijcnn1_like
    from repro.models.small import logreg_init, logreg_loss
    from repro.optim.fused import FusedAMSGrad
    from repro.utils.hlo_cost import donation_aliases

    prev = _load_baseline()
    m = 10
    ds = ijcnn1_like(n=4000)
    mtx = pad_to_matrix(uniform_partition(ds.n, m, seed=0))
    sample = make_sampler(ds.x, ds.y, mtx, 32)
    params = logreg_init(None, 22, 2)
    out = {"iters": iters, "workers": m}

    # compile all arms first, then INTERLEAVE the timed runs (best-of-N):
    # the gating_overhead_frac is a ratio, and sequential phases would
    # fold machine drift into it on shared boxes.
    variants = {
        "always": dict(kind="always"),
        "cada2": dict(kind="cada2"),
        "cada2_unfused": dict(kind="cada2", fuse_evals=False),
    }
    arms = {}
    batches = jax.vmap(sample)(
        jax.random.split(jax.random.PRNGKey(1), iters))
    for name, spec in variants.items():
        eng = CADAEngine(logreg_loss, FusedAMSGrad(lr=0.01),
                         CommRule(kind=spec["kind"], c=0.6, d_max=10,
                                  max_delay=100), m,
                         fuse_evals=spec.get("fuse_evals"))
        st = eng.init(params)
        compiled = jax.jit(eng.run, donate_argnums=(0,)).lower(
            st, batches).compile()
        aliased = donation_aliases(compiled.as_text())
        if aliased == 0:
            print("[cada] WARNING: donated engine state did not alias — "
                  "every run copies the full state", file=sys.stderr)
        st1, mets = compiled(jax.tree.map(lambda x: x.copy(), st),
                             batches)           # steady-state warmup
        jax.block_until_ready(st1.params)
        arms[name] = {"compiled": compiled, "st": st, "mets": mets,
                      "eng": eng, "aliased": aliased, "dt": float("inf")}
    for _ in range(5):
        for name, arm in arms.items():
            fresh = jax.tree.map(lambda x: x.copy(), arm["st"])
            t0 = time.time()
            st2, arm["mets"] = arm["compiled"](fresh, batches)
            jax.block_until_ready(st2.params)
            arm["dt"] = min(arm["dt"], time.time() - t0)
    for name, arm in arms.items():
        mets = arm["mets"]
        state_b, eval_b = _comm_state_bytes(arm["st"].comm)
        out[name] = {
            "steps_per_sec": round(iters / arm["dt"], 1),
            "final_loss": float(np.asarray(mets["loss"])[-20:].mean()),
            "uploads": int(np.asarray(mets["uploads"]).sum()),
            "mbytes_up": float(np.asarray(mets["bytes_up"]).sum() / 1e6),
            "donation_aliases": arm["aliased"],
            "worker_state_bytes": state_b,
            "eval_point_bytes": eval_b,
            "second_eval_frac": _second_eval_frac(
                arm["eng"], arm["st"], batches, arm["dt"] / iters),
        }
        _warn_if_regressed(f"engine-{name}", out[name]["steps_per_sec"],
                           (prev or {}).get(name))
    out["uploads_saved_frac"] = round(
        1.0 - out["cada2"]["uploads"] / out["always"]["uploads"], 3)
    out["gating_overhead_frac"] = round(
        1.0 - out["cada2"]["steps_per_sec"]
        / out["always"]["steps_per_sec"], 4)
    out["gating_overhead_frac_unfused"] = round(
        1.0 - out["cada2_unfused"]["steps_per_sec"]
        / out["always"]["steps_per_sec"], 4)
    out["m_sweep"] = _bench_m_sweep()

    lm = bench_trainer_lm(lm_steps)
    out.update(lm)
    for name in ("trainer_lm", "sharded_flat", "sharded_perleaf_ref"):
        _warn_if_regressed(f"trainer-{name}", lm[name]["steps_per_sec"],
                           (prev or {}).get(name))

    with open(BENCH_PATH, "w") as f:
        json.dump(out, f, indent=1)
    print(f"[cada] {out['cada2']['steps_per_sec']} steps/s "
          f"(gating overhead {out['gating_overhead_frac']:.1%}), "
          f"{out['uploads_saved_frac']:.0%} uploads saved, "
          f"trainer-LM {out['trainer_lm']['steps_per_sec']} steps/s "
          f"(sharded-state hparams: flat "
          f"{out['sharded_flat']['steps_per_sec']} vs old per-leaf "
          f"fallback {out['sharded_perleaf_ref']['steps_per_sec']}) "
          f"-> {BENCH_PATH}", file=sys.stderr)
    return out


def _bench_m_sweep(ms=(10, 256, 2048), iters=(300, 100, 15),
                   cohort_c=64) -> dict:
    """The federated-magnitude micro-arm: cada2 (default eval dispatch) at
    M = 10 / 256 / 2048 on logreg, arms compiled first then INTERLEAVED
    best-of-3 — per M: steps/sec, the ring's eval-point bytes, and the
    dense O(M·n) plane it replaced. The ring holds R = min(M, D)+1 rows,
    so eval-point state saturates at (D+1)·n while the dense equivalent
    grows with M.

    The ``{M}/cohort{C}`` arm runs the SAME largest-M problem on the
    cohort-virtualized plane (host :class:`repro.core.flat.WorkerPool`,
    C sampled rows gathered per round): per-round compute drops from M
    gradient evaluations + an M-row aggregate to C of each, so its
    steps/sec over the dense arm is the tentpole's measured win. Every
    arm records the device/host byte split: the dense plane keeps the
    whole O(M·n) worker plane device-resident (``host_pool_bytes`` = 0),
    the cohort arm keeps O(C·n) on device and parks O(M·n) on the host.

    ``{M}/cohort{C}`` is the serial transfer oracle (``pipeline=False``);
    ``.../pipelined`` double-buffers the pool traffic under device
    compute and ``.../pipelined/memmap`` runs the same pipeline over a
    disk-backed pool. All three ride the same jitted step, so
    ``speedup_vs_serial`` isolates the transfer time the overlap hides;
    each arm also reports its per-round ``gather_ms/step_ms/scatter_ms``
    host-side phase breakdown, read from the obs trace recorder's
    ``"pipeline"``-track span aggregates (the one home for per-round
    phase timing — no bench-side clock arithmetic).
    """
    import jax
    import numpy as np

    from repro.core.engine import CADAEngine, make_sampler, sample_cohorts
    from repro.core.flat import layout_of
    from repro.core.rules import CommRule
    from repro.data.partition import pad_to_matrix, uniform_partition
    from repro.data.synthetic import ijcnn1_like
    from repro.models.small import logreg_init, logreg_loss
    from repro.obs.trace import Tracer
    from repro.optim.fused import FusedAMSGrad

    d = 100
    rule = CommRule(kind="cada2", c=0.6, d_max=10, max_delay=d)
    params = logreg_init(None, 22, 2)
    n_flat = layout_of(params).n_flat
    arms = {}
    for m, its in zip(ms, iters):
        ds = ijcnn1_like(n=max(4000, 2 * m))
        mtx = pad_to_matrix(uniform_partition(ds.n, m, seed=0))
        sample = make_sampler(ds.x, ds.y, mtx, 8)
        batches = jax.vmap(sample)(
            jax.random.split(jax.random.PRNGKey(1), its))
        eng = CADAEngine(logreg_loss, FusedAMSGrad(lr=0.01), rule, m)
        st = eng.init(params)
        compiled = jax.jit(eng.run, donate_argnums=(0,)).lower(
            st, batches).compile()
        st1, _ = compiled(jax.tree.map(lambda x: x.copy(), st), batches)
        jax.block_until_ready(st1.params)
        arms[m] = {"compiled": compiled, "st": st, "batches": batches,
                   "iters": its, "dt": float("inf")}

    # cohort arms: same rule/problem/batch stream as the largest dense M,
    # only the C sampled rows exist on device per round. Three variants,
    # interleaved with the dense arms: the serial oracle
    # (pipeline=False), the double-buffered pipeline, and the pipeline
    # over a disk-backed memmap pool — the pipelined-vs-serial delta is
    # the transfer time the overlap hides, measured within ONE run.
    import shutil
    import tempfile

    m_big, its_big = ms[-1], iters[-1]
    eng_c = CADAEngine(logreg_loss, FusedAMSGrad(lr=0.01), rule, m_big)
    cohorts = sample_cohorts(m_big, cohort_c, its_big, seed=1)
    cohort_batches = [
        jax.tree.map(lambda x, i=i: x[i][cohorts[i]],
                     arms[m_big]["batches"]) for i in range(its_big)]
    memmap_dir = tempfile.mkdtemp(prefix="bench_pool_")
    variants = {
        "serial": {"pipeline": False, "storage": "ram", "path": None},
        "pipelined": {"pipeline": True, "storage": "ram", "path": None},
        "pipelined/memmap": {"pipeline": True, "storage": "memmap",
                             "path": memmap_dir},
    }

    def fresh_cohort(v):
        st, pool = eng_c.init_cohort(params, pool_storage=v["storage"],
                                     pool_path=v["path"])
        jax.block_until_ready(st.params_flat)
        return st, pool

    for v in variants.values():                         # compile + warmup
        st_w, pool_w = fresh_cohort(v)
        st_w, _ = eng_c.run_cohort(st_w, pool_w, cohort_batches, cohorts,
                                   pipeline=v["pipeline"])
        jax.block_until_ready(st_w.params_flat)
        v.update(dt=float("inf"), trace=Tracer(), pool=pool_w)

    for _ in range(3):
        for m, arm in arms.items():
            fresh = jax.tree.map(lambda x: x.copy(), arm["st"])
            t0 = time.time()
            st2, _ = arm["compiled"](fresh, arm["batches"])
            jax.block_until_ready(st2.params)
            arm["dt"] = min(arm["dt"], time.time() - t0)
        for v in variants.values():
            st_c, pool_c = fresh_cohort(v)
            tr = Tracer()
            t0 = time.time()
            st_c, _ = eng_c.run_cohort(st_c, pool_c, cohort_batches,
                                       cohorts, pipeline=v["pipeline"],
                                       trace=tr)
            jax.block_until_ready(st_c.params_flat)
            dt = time.time() - t0
            if dt < v["dt"]:
                v.update(dt=dt, trace=tr, pool=pool_c)
    shutil.rmtree(memmap_dir, ignore_errors=True)
    sweep = {}
    for m, arm in arms.items():
        _, eval_b = _comm_state_bytes(arm["st"].comm)
        sweep[str(m)] = {
            "workers": m,
            "iters": arm["iters"],
            "steps_per_sec": round(arm["iters"] / arm["dt"], 1),
            "ring_rows": min(m, d) + 1,
            "eval_point_bytes": eval_b,
            "dense_equiv_bytes": m * n_flat * 4,
            "device_worker_plane_bytes": m * n_flat * 4,
            "host_pool_bytes": 0,
        }
    sps_serial = round(its_big / variants["serial"]["dt"], 1)
    if sps_serial < 5 * sweep[str(m_big)]["steps_per_sec"]:
        print(f"[cada] WARNING: cohort arm at M={m_big} C={cohort_c} is "
              f"{sps_serial} steps/s vs dense "
              f"{sweep[str(m_big)]['steps_per_sec']} — below the 5x the "
              f"O(C·n) plane is supposed to buy", file=sys.stderr)
    for name, v in variants.items():
        sps = round(its_big / v["dt"], 1)
        pool_v = v["pool"]
        # per-round phase breakdown straight off the trace recorder's
        # span aggregates: {phase: {count, total_s, max_s}}
        agg = v["trace"].aggregate("pipeline")
        rounds = max(1, agg.get("step", {}).get("count", its_big))

        def phase_ms(phase, agg=agg, rounds=rounds):
            return round(agg.get(phase, {}).get("total_s", 0.0)
                         / rounds * 1e3, 3)

        key = (f"{m_big}/cohort{cohort_c}" if name == "serial"
               else f"{m_big}/cohort{cohort_c}/{name}")
        sweep[key] = {
            "workers": m_big,
            "cohort": cohort_c,
            "iters": its_big,
            "pipeline": v["pipeline"],
            "pool_storage": v["storage"],
            "steps_per_sec": sps,
            "gather_ms": phase_ms("gather"),
            "step_ms": phase_ms("step"),
            "scatter_ms": phase_ms("scatter"),
            "patch_ms": phase_ms("patch"),
            "device_worker_plane_bytes": pool_v.device_row_bytes(cohort_c),
            "host_pool_bytes": pool_v.nbytes,
            "host_pool_mapped_bytes": pool_v.mapped_nbytes,
            "host_pool_resident_bytes": pool_v.resident_nbytes,
            "speedup_vs_dense": round(
                sps / sweep[str(m_big)]["steps_per_sec"], 2),
        }
        if name != "serial":
            sweep[key]["speedup_vs_serial"] = round(sps / sps_serial, 2)
    if sweep[f"{m_big}/cohort{cohort_c}/pipelined"]["speedup_vs_serial"] \
            < 1.0:
        print(f"[cada] WARNING: pipelined cohort arm did not beat the "
              f"serial oracle within this run "
              f"({sweep[f'{m_big}/cohort{cohort_c}/pipelined']})",
              file=sys.stderr)
    return sweep


def bench_trainer_lm(steps: int = 30) -> dict:
    """Hierarchical-CADA trainer throughput on the (smoke) LM path.

    Three arms, INTERLEAVED per the 2-core caution (sequential phases
    fold machine drift into the comparison):

      * ``trainer_lm``       — the default hparams (fused flat plane);
      * ``sharded_flat``     — the same rule at
        ``state_fsdp_axes=("data",)``: the hparams that USED to force the
        per-leaf fallback (``_flat_enabled``) and now run the fused flat
        plane (mesh-free here, so same program as ``trainer_lm`` — a
        same-program control for the entry below);
      * ``sharded_perleaf_ref`` — those hparams on the per-leaf pytree
        path (``fused=False``), i.e. what the deleted fallback actually
        ran. ``sharded_flat`` vs ``sharded_perleaf_ref`` IS the
        fork-deletion perf trace: the speedup these policies gained by
        moving onto the flat plane.
    """
    import jax
    import numpy as np

    import repro.configs as C
    from repro.core.rules import CommRule
    from repro.distributed.trainer import (TrainHParams, init_train_state,
                                           make_train_step, worker_split)

    arch = "stablelm-1.6b"
    cfg = C.get_smoke_config(arch)
    m = 2
    rule = CommRule(kind="cada2", c=0.6, d_max=10, max_delay=50)
    variants = {
        "trainer_lm": TrainHParams(rule=rule, lr=1e-3),
        "sharded_flat": TrainHParams(rule=rule, lr=1e-3,
                                     state_fsdp_axes=("data",)),
        "sharded_perleaf_ref": TrainHParams(rule=rule, lr=1e-3,
                                            state_fsdp_axes=("data",),
                                            fused=False),
    }
    batch = worker_split(
        {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 65), 0,
                                      cfg.vocab)}, m)

    arms = {}
    for name, hp in variants.items():
        step = jax.jit(make_train_step(cfg, hp, m), donate_argnums=(0,))
        st0 = init_train_state(cfg, hp, m, jax.random.PRNGKey(0))

        def fresh(st0=st0):
            # the step donates its state, so each rep gets copies of st0
            return jax.tree.map(lambda x: x.copy(), st0)

        st, mets = step(fresh(), batch)      # compile + warmup
        jax.block_until_ready(st.params)
        arms[name] = {"step": step, "fresh": fresh, "mets": mets,
                      "dt": float("inf")}
    for _ in range(3):                       # best-of-3, arms interleaved
        for name, arm in arms.items():
            # re-init per rep: continuing one trajectory across reps would
            # time DIFFERENT upload regimes (CADA uploads thin out as
            # training advances), making later reps incomparably cheaper
            st = arm["fresh"]()
            jax.block_until_ready(st)  # async state copy off the clock
            t0 = time.time()
            for _ in range(steps):
                st, arm["mets"] = arm["step"](st, batch)
            jax.block_until_ready(st.params)
            arm["dt"] = min(arm["dt"], time.time() - t0)
    return {name: {"arch": f"{arch}(smoke)", "workers": m, "rule": "cada2",
                   "state_fsdp_axes": list(variants[name].state_fsdp_axes),
                   "fused": variants[name].fused,
                   "steps_per_sec": round(steps / arm["dt"], 1),
                   "final_loss": float(np.asarray(arm["mets"]["loss"]))}
            for name, arm in arms.items()}


def bench_sim(iters: int = 300) -> dict:
    """Wall-clock CADA tracker, written to ``BENCH_sim.json``: the
    discrete-event runtime (repro.sim) prices the logreg trajectories
    under a zero-latency LAN and a WAN profile.

    The two committed claims (asserted here, so the JSON always records a
    state where they hold):

      * **WAN**: at least one compressed-upload rule (laq 8-bit / topk
        sparse-wire) beats ``always`` on simulated time-to-target-loss —
        skipping rounds AND shrinking wires earns wall-clock when uploads
        are expensive;
      * **zero-latency LAN**: ``always`` wins — when communication is
        free, the per-iteration-best rule is the wall-clock-best rule,
        and gating buys nothing.

    Plus the ``federated`` arm: the same MLP at **M = 10⁴ workers**,
    C = 64 cohort rounds on the cohort-virtualized plane
    (``cohort_size=``). The O(M·n) worker planes live in the host
    :class:`repro.core.flat.WorkerPool`; the device sees O(C·n) rows
    per round, so the scenario fits where a dense plane (which would
    materialize the (M, n_flat) plane AND an (iters, M, b, ...) batch
    stream on device) cannot — the CI ``federated-smoke`` leg re-runs
    this magnitude under a 6 GiB ``ulimit -v`` to pin that.

    Deterministic: fixed seeds, deterministic compute/link models — the
    committed file reproduces exactly (steps/sec caveats of BENCH_cada
    don't apply; simulated seconds are computed, not measured).
    """
    import jax

    # the problem (the ~1.6k-param MLP — on the 1 Mbit/s WAN uplink the
    # dense plane costs ~51 ms/upload, so the wire width is a first-order
    # wall-clock term) and the rule table are SHARED with
    # ablations.sweep_network: BENCH_sim.json and the sweep always
    # describe the same scenario
    from benchmarks.ablations import M as m, _mlp_problem, network_rules
    from repro.core.rules import CommRule
    from repro.models.small import mlp_loss
    from repro.sim import network_profile, simulate, summarize

    target = 0.05
    sample, params = _mlp_problem()
    loss_fn = mlp_loss
    batches = jax.vmap(sample)(
        jax.random.split(jax.random.PRNGKey(1), iters))
    rules = network_rules()

    # the adaptive local-steps arm: same problem, batches carrying a
    # (rounds, H, M, b, ·) local axis padded to the adaptation cap. Each
    # worker's H_m follows comm-vs-compute time (avp's period rule
    # generalized to local steps), so on the WAN H rides the cap (~16
    # local steps amortize one ~98 ms round trip) while on the free LAN
    # it shrinks to per-iteration rounds.
    h_pad, lrounds = 16, 120
    lbatches = jax.vmap(sample)(
        jax.random.split(jax.random.PRNGKey(2), lrounds * h_pad))
    lbatches = jax.tree.map(
        lambda x: x.reshape((lrounds, h_pad) + x.shape[1:]), lbatches)
    local_rule = CommRule(kind="local_momentum", c=0.6, d_max=10,
                          max_delay=100, adapt_local_steps=True,
                          local_steps_max=h_pad, local_lr=0.05)

    # the fused second-eval discount (ComputeModel.second_eval_factor):
    # cada2's stacked two-point eval was measured (BENCH_cada,
    # second_eval_frac / gating_overhead) at roughly HALF the cost of a
    # full second pass, so the ``cada2/fused-eval`` arm prices eval_idx≥1
    # at 0.5 — wall-clock stops double-charging the optimization while
    # the plain ``cada2`` row keeps the paper's flat 2-evals pricing.
    fused_factor = 0.5
    out = {"iters": iters, "workers": m, "target_loss": target,
           "second_eval_factor_fused": fused_factor,
           "profiles": {}}
    for profile in ("zero", "wan"):
        prows = {}
        for name, rule in rules.items():
            res = simulate(loss_fn, rule, params, batches,
                           n_workers=m, network=profile, mode="barrier",
                           lr=0.01)
            prows[name] = summarize(res, target)
        # one bounded-staleness async arm on the same scenario (M× the
        # server versions: an async step carries 1/M of a sync round)
        res = simulate(loss_fn, rules["laq"], params, batches,
                       n_workers=m, network=profile, mode="async",
                       async_tau=20, lr=0.01)
        prows["laq/async"] = summarize(res, target)
        # cada2 with the second eval priced at the measured stacked cost
        # (same trajectory as the plain cada2 row — only compute pricing
        # differs, so the delta is pure second-eval wall-clock)
        prof_fused = network_profile(profile, m,
                                     second_eval_factor=fused_factor)
        res = simulate(loss_fn, rules["cada2"], params, batches,
                       n_workers=m, network=prof_fused, mode="barrier",
                       lr=0.01)
        prows["cada2/fused-eval"] = summarize(res, target)
        # adaptive local steps on this profile; the realized per-round
        # mean H is recorded so the JSON shows WHERE the cadence landed
        res = simulate(loss_fn, local_rule, params, lbatches,
                       n_workers=m, network=profile, mode="barrier",
                       lr=0.01)
        prows["local/adapt"] = {
            **summarize(res, target),
            "mean_local_steps": round(
                float(res.metrics["local_steps"].mean()), 2),
            "final_local_steps": round(
                float(res.metrics["local_steps"][-1].mean()), 2)}
        times = {k: v["time_to_target_s"] for k, v in prows.items()
                 if v["time_to_target_s"] is not None}
        winner = min(times, key=times.get) if times else None
        out["profiles"][profile] = {"rules": prows,
                                    "time_to_target_s": times,
                                    "winner": winner}
        print(f"[sim] {profile}: winner {winner} "
              f"({ {k: round(v, 4) for k, v in times.items()} })",
              file=sys.stderr)

    # the subsystem's acceptance claims, pinned: compressed wires win
    # wall-clock where uploads are expensive, never where they are free.
    # (A rule that never settles at the target is absent from `times` —
    # it loses against any rule that did.)
    wan = out["profiles"]["wan"]["time_to_target_s"]
    zero = out["profiles"]["zero"]["time_to_target_s"]
    compressed = [wan[k] for k in ("laq", "topk") if k in wan]
    assert compressed, f"no compressed rule reached the target on wan: {wan}"
    assert "always" not in wan or min(compressed) < wan["always"], wan
    assert "always" in zero, f"always never reached the target on zero: " \
        f"{zero}"
    assert zero["always"] <= min((zero[k] for k in ("laq", "topk")
                                  if k in zero), default=float("inf")), zero
    # the local-steps axis's claim: on the WAN, adapting the PAYLOAD
    # CADENCE (H local steps per delta upload) beats the best
    # per-iteration gating rule outright — rounds amortize the link
    # latency instead of merely skipping some uploads. On the free LAN
    # the ordering flips (H shrinks to 1 and the sgd(1.0)-server
    # averaging loses to gated Adam); recorded above, not asserted.
    gating = [wan[k] for k in ("always", "cada2", "laq", "topk")
              if k in wan]
    assert "local/adapt" in wan, \
        f"adaptive local steps never reached the target on wan: " \
        f"{out['profiles']['wan']['rules']['local/adapt']}"
    assert wan["local/adapt"] < min(gating), wan

    out["federated"] = _bench_sim_federated(params, loss_fn, rules)

    with open(SIM_BENCH_PATH, "w") as f:
        json.dump(out, f, indent=1)
    print(f"[sim] -> {SIM_BENCH_PATH}", file=sys.stderr)
    return out


def _bench_sim_federated(params, loss_fn, rules,
                         m=10_000, c=64, rounds=60) -> dict:
    """The federated-magnitude arm of ``BENCH_sim.json``: the bench_sim
    MLP at M = 10⁴ workers, C-worker cohort rounds over the WAN profile.
    Batches come from :func:`repro.core.engine.make_cohort_sampler`
    (O(C·b) per round, never the (rounds, M, b, ...) dense stream), the
    worker planes from the host pool. The recorded byte split IS the
    tentpole claim: ``host_pool_bytes`` is the O(M·n) plane a dense run
    would hold on device, ``device_worker_plane_bytes`` the O(C·n) the
    cohort run actually does.

    lr is 1e-3 (not the LAN/WAN rows' 0.01): the eq. (3) aggregate
    divides the C uploaded rows by M, so at C/M = 0.64% the server's
    Adam direction is far noisier than at full participation and 0.01
    oscillates. Per-round losses stay noisy regardless — every worker
    holds 2 samples, and each round evaluates a fresh cohort."""
    import jax
    import numpy as np

    from repro.core.engine import make_cohort_sampler
    from repro.data.partition import pad_to_matrix, uniform_partition
    from repro.data.synthetic import ijcnn1_like
    from repro.sim import simulate, summarize

    ds = ijcnn1_like(n=2 * m)
    mtx = pad_to_matrix(uniform_partition(ds.n, m, seed=0))
    csample = make_cohort_sampler(ds.x, ds.y, mtx, 32)

    def batches(k, cohort):
        return csample(jax.random.PRNGKey(k), cohort)

    res = simulate(loss_fn, rules["cada2"], params, batches,
                   n_workers=m, network="wan", mode="barrier", lr=1e-3,
                   cohort_size=c, rounds=rounds)
    row = {"workers": m, "cohort_size": c, "rounds": rounds,
           "rule": "cada2",
           "host_pool_bytes": int(res.metrics["host_pool_bytes"]),
           "device_worker_plane_bytes": int(
               res.metrics["device_worker_plane_bytes"]),
           **summarize(res)}
    # the cohort plane's point, pinned in the committed JSON: device
    # worker-plane bytes are C/M of the pool (>100x smaller here), and
    # the run still LEARNS (deterministic seeds, so not flaky)
    assert row["device_worker_plane_bytes"] * (m // c) \
        <= row["host_pool_bytes"], row
    assert row["final_loss"] < float(np.asarray(res.losses)[0]), row
    print(f"[sim] federated M={m} C={c}: "
          f"{row['device_worker_plane_bytes']} device B vs "
          f"{row['host_pool_bytes']} host-pool B, "
          f"final_loss={row['final_loss']:.4f}", file=sys.stderr)
    return row


def bench_hierarchical(steps: int = 40) -> dict:
    """Hierarchical-CADA DCN-savings tracker, written to
    ``BENCH_hierarchical.json`` (previously its numbers only landed in
    the orphaned ``results/hierarchical_cada.json``)."""
    from benchmarks import hierarchical_cada

    rows = hierarchical_cada.run(steps=steps)
    by_rule = {r["rule"]: r for r in rows}
    always, cada = by_rule["always"], by_rule["cada2"]
    out = {
        "steps": steps,
        "rows": rows,
        "dcn_saved_frac": round(
            1.0 - cada["dcn_gbytes"] / always["dcn_gbytes"], 3),
        "delta_final_loss": round(
            cada["final_loss"] - always["final_loss"], 4),
    }
    with open(HIER_BENCH_PATH, "w") as f:
        json.dump(out, f, indent=1)
    print(f"[hier] DCN saved {out['dcn_saved_frac']:.0%} at "
          f"dloss={out['delta_final_loss']:+.4f} -> {HIER_BENCH_PATH}",
          file=sys.stderr)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-list: logreg,nn,lag,hierarchical,"
                         "ablations,roofline,cada,sim")
    args = ap.parse_args()
    from repro.launch.cache import init_compile_cache
    init_compile_cache()
    full = args.full
    only = set(args.only.split(",")) if args.only else None

    rows = []

    def emit(bench, r):
        r = dict(r)
        r["bench"] = bench
        rows.append(r)

    if only is None or "cada" in only:
        b = bench_cada(iters=600 if full else 300)
        for kind in ("always", "cada2"):
            emit("bench_cada(BENCH_cada.json)",
                 {"rule": kind, **b[kind]})

    if only is None or "logreg" in only:
        from benchmarks import paper_logreg
        t0 = time.time()
        for ds in ("covtype", "ijcnn1"):
            for r in paper_logreg.run(ds, iters=1000 if full else 500,
                                      monte_carlo=3 if full else 1):
                emit("paper_logreg(Fig2-3)", r)
        print(f"[logreg done in {time.time() - t0:.0f}s]", file=sys.stderr)

    if only is None or "nn" in only:
        from benchmarks import paper_nn
        t0 = time.time()
        for model in (("cnn", "mlp") if full else ("mlp",)):
            for r in paper_nn.run(model=model,
                                  iters=800 if full else 300):
                emit("paper_nn(Fig4)", r)
        print(f"[nn done in {time.time() - t0:.0f}s]", file=sys.stderr)

    if only is None or "lag" in only:
        from benchmarks import lag_ineffectiveness
        for r in lag_ineffectiveness.run(iters=800 if full else 400):
            emit("lag_ineffectiveness(§2.1)", r)

    if only is None or "sim" in only:
        b = bench_sim(iters=600 if full else 300)
        for profile, p in b["profiles"].items():
            for rule, r in p["rules"].items():
                emit("bench_sim(BENCH_sim.json)",
                     {"rule": rule, "profile": profile, **r})

    if only is None or {"hier", "hierarchical"} & only:
        b = bench_hierarchical(steps=80 if full else 40)
        for r in b["rows"]:
            emit("hierarchical_cada(BENCH_hierarchical.json)", r)

    if only is None or "ablations" in only:
        from benchmarks import ablations
        iters = 600 if full else 300
        for r in (ablations.sweep_c(iters) + ablations.sweep_D(iters)
                  + ablations.sweep_bits(iters)
                  + ablations.sweep_rules(iters)
                  + ablations.sweep_avp(iters)
                  + ablations.sweep_network(min(iters, 300))
                  + ablations.sweep_H(iters)):
            emit("ablations(supplement)", r)

    if only is None or "roofline" in only:
        from benchmarks import roofline
        rl = roofline.load(["results/dryrun_single.jsonl",
                            "results/dryrun_multi.jsonl"])
        for r in rl:
            emit("roofline(§Dry-run)", {
                "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
                "dominant": r["dominant"],
                "t_compute_s": r["t_compute_s"],
                "t_memory_s": r["t_memory_s"],
                "t_collective_s": r["t_collective_s"],
                "useful": r["useful_flops_ratio"]})

    # ------------------------------------------------------------- CSV out
    keys = ["bench"]
    for r in rows:
        for k in r:
            if k not in keys:
                keys.append(k)
    print(",".join(keys))
    for r in rows:
        print(",".join(str(r.get(k, "")) for k in keys))


if __name__ == "__main__":
    main()
