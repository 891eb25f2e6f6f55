"""Training launcher: ``python -m repro.launch.train --arch <id> ...``

Runs the hierarchical-CADA trainer on whatever devices exist (the host
mesh), with checkpointing and metric logging. On a real TPU fleet the same
code runs under the production meshes of launch/mesh.py (the dry-run proves
every assigned architecture lowers against those).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import time
from typing import Any, NamedTuple

import jax
import numpy as np

import repro.configs as C
from repro.checkpoint import io as ckpt
from repro.core.comm import STRATEGIES, strategy_kinds
from repro.core.rules import CommRule
from repro.data.synthetic import lm_tokens
from repro.distributed.trainer import (TrainHParams, flat_state_shards,
                                       init_train_state, jit_train_step,
                                       make_train_step, place_train_state,
                                       worker_split)
from repro.launch.cache import init_compile_cache
from repro.launch.mesh import make_host_mesh


class MeshRun(NamedTuple):
    """What :func:`run_mesh` leaves behind."""
    state: Any       # final DistTrainState
    history: list    # one row per logged step (scalars + upload_mask)
    step: Any        # the jitted step
    batch: dict      # its first batch (lower ``step`` on it to inspect)


def make_token_batches(cfg, *, global_batch, seq, steps, seed=0):
    """Zipfian LM stream -> (steps, B, S+1) token batches."""
    toks = lm_tokens(steps * global_batch * (seq + 1) + 1, cfg.vocab,
                     seed=seed)
    n = steps * global_batch * (seq + 1)
    return toks[:n].reshape(steps, global_batch, seq + 1)


def _flatten_row(row: dict, prefix: str = "") -> dict:
    """One-level flatten of nested dicts into metric-name keys."""
    out = {}
    for k, v in row.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flatten_row(v, prefix=f"{key}_"))
        else:
            out[key] = v
    return out


def _write_obs(args, tracer, row: dict) -> None:
    """Write the requested telemetry sinks: Chrome-trace JSON
    (``--trace``), metrics JSONL (``--metrics-out``), Prometheus
    textfile (``--metrics-prom``)."""
    from repro.obs import MetricsRegistry, write_chrome_trace, write_jsonl

    if args.trace and tracer:
        write_chrome_trace(tracer, args.trace,
                           meta={"arch": args.arch, "rule": args.rule,
                                 "runtime": args.runtime})
        print(f"[obs] chrome trace ({len(tracer.events)} events, "
              f"{len(tracer.tracks)} tracks) -> {args.trace}")
    if args.metrics_out:
        write_jsonl(args.metrics_out, row)
        print(f"[obs] metrics jsonl -> {args.metrics_out}")
    if args.metrics_prom:
        reg = MetricsRegistry()
        for k, v in _flatten_row(row).items():
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            reg.gauge(k).set(v)
        reg.write_prom(args.metrics_prom)
        print(f"[obs] prometheus textfile -> {args.metrics_prom}")


def run_sim(cfg, rule, args) -> None:
    """`--runtime sim`: train under the discrete-event heterogeneous-
    cluster runtime (repro.sim) — simulated wall-clock under the chosen
    network profile, synchronous barrier or bounded-staleness async
    (`--async-tau`). No mesh: workers are simulated processes.
    `--trace` exports every simulated compute/transfer/gate event as a
    span on the simulated clock (one track per worker + a server track)."""
    import jax.numpy as jnp

    from repro.models.model import init_params, lm_loss
    from repro.obs import Tracer
    from repro.sim import simulate, summarize

    m = args.workers or 4
    steps = args.steps
    toks = make_token_batches(cfg, global_batch=args.global_batch,
                              seq=args.seq, steps=steps)
    # delta-payload rules consume (H, M, b, ·) per round; adaptive H runs
    # against batches padded to the adaptation cap (the realized schedule
    # masks each worker's scan to its own H_m)
    h = _round_local_steps(rule, args)
    per_step = [worker_split({"tokens": toks[i]}, m, local_steps=h)
                for i in range(steps)]
    batches = jax.tree.map(lambda *xs: jnp.stack(xs), *per_step)

    mode = "async" if args.async_tau else "barrier"
    tracer = Tracer() if args.trace else None
    params = init_params(cfg, jax.random.PRNGKey(0))
    res = simulate(lambda p, wb: lm_loss(cfg, p, wb)[0], rule, params,
                   batches, n_workers=m, network=args.network, mode=mode,
                   async_tau=args.async_tau,
                   participation=args.participation,
                   cohort_size=args.cohort_size,
                   host_pool=bool(args.async_tau
                                  and (args.host_pool or args.pool_memmap)),
                   pipeline=not args.no_pipeline,
                   metrics_every=args.metrics_every,
                   pool_storage="memmap" if args.pool_memmap else "ram",
                   pool_path=args.pool_memmap or None, lr=args.lr,
                   eval_s=args.sim_eval_ms * 1e-3, trace=tracer)
    row = summarize(res, args.target_loss or None)
    print(f"[sim] {args.network}/{mode} rule={rule.kind}: "
          f"{res.steps} server steps in {res.wall_s:.3f} simulated s, "
          f"loss {row['final_loss']:.4f}, uploads {res.uploads}, "
          f"up {row['mbytes_up']:.3f} MB, "
          f"utilization {row['utilization_mean']:.2f}")
    print(json.dumps(row, indent=1))
    _write_obs(args, tracer, row)


def _round_local_steps(rule: CommRule, args) -> int:
    """Local-step axis H of one round's batch: the adaptation cap for
    adaptive-H runs, the fixed period otherwise, 1 for gradient-payload
    rules. Validates the global batch divides into H · M slices."""
    if not STRATEGIES[rule.kind].delta_payload:
        return 1
    h = (rule.resolved_local_steps_max if rule.adapt_local_steps
         else rule.local_steps)
    m = args.workers or 4
    if args.global_batch % (h * m):
        raise SystemExit(
            f"--global-batch {args.global_batch} must divide into "
            f"local_steps*workers = {h}*{m} per-local-step slices")
    return h


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", required=True, choices=C.list_archs())
    p.add_argument("--smoke", action="store_true",
                   help="reduced config (CPU-sized)")
    p.add_argument("--runtime", default="mesh", choices=["mesh", "sim"],
                   help="mesh = run on the host devices; sim = the "
                        "discrete-event heterogeneous-cluster runtime "
                        "(repro.sim) — simulated wall-clock under "
                        "--network, no accelerator mesh")
    p.add_argument("--network", default="lan",
                   help="sim runtime: network profile "
                        "(zero | lan | wan | hetero)")
    p.add_argument("--async-tau", type=int, default=0,
                   help="sim runtime: >0 runs the bounded-staleness ASYNC "
                        "mode with staleness cap tau (uploads applied as "
                        "they arrive); 0 = synchronous barrier mode")
    p.add_argument("--participation", type=float, default=1.0,
                   help="sim barrier mode: fraction of workers "
                        "participating per round")
    p.add_argument("--cohort-size", type=int, default=0,
                   help="sim barrier mode: >0 runs the FEDERATED cohort "
                        "plane — C sampled workers per round through the "
                        "host WorkerPool, O(C*n) device state")
    p.add_argument("--no-pipeline", action="store_true",
                   help="cohort rounds: disable the double-buffered "
                        "transfer pipeline (serial parity oracle)")
    p.add_argument("--metrics-every", type=int, default=8,
                   help="cohort rounds: fetch device-side metrics every "
                        "K rounds instead of per round")
    p.add_argument("--host-pool", action="store_true",
                   help="sim async mode: stream per-worker rows through "
                        "the host WorkerPool instead of holding the "
                        "(M, n) plane on device (implied by "
                        "--pool-memmap; this flag enables the RAM-backed "
                        "pool without memmap spill)")
    p.add_argument("--pool-memmap", default="",
                   help="back the WorkerPool's O(M*n) planes with "
                        "np.memmap files under this directory (M beyond "
                        "RAM); empty = RAM")
    p.add_argument("--sim-eval-ms", type=float, default=1.0,
                   help="sim runtime: simulated milliseconds per worker "
                        "gradient evaluation")
    p.add_argument("--target-loss", type=float, default=0.0,
                   help="sim runtime: report simulated "
                        "time-to-target-loss for this target (0 = off)")
    p.add_argument("--rule", default="cada2", choices=list(strategy_kinds()),
                   help="communication rule; every strategy registered in "
                        "repro.core.comm is launchable")
    p.add_argument("--quantize-bits", type=int, default=0,
                   help="b-bit innovation uploads (0 = rule default)")
    p.add_argument("--topk-frac", type=float, default=0.1,
                   help="topk rule: fraction of innovation entries "
                        "uploaded per (worker, leaf)")
    p.add_argument("--sparse-wire", action="store_true",
                   help="topk rule: ship (values, indices) pairs sized k "
                        "through the gated collective instead of the "
                        "dense masked plane")
    p.add_argument("--no-error-feedback", action="store_true",
                   help="laq/topk: drop the compression error instead of "
                        "carrying the per-worker residual e_m")
    p.add_argument("--period-min", type=int, default=1,
                   help="avp rule: per-worker upload-period lower bound")
    p.add_argument("--period-max", type=int, default=0,
                   help="avp rule: upper bound (0 = max-delay)")
    p.add_argument("--avp-compose", action="store_true",
                   help="avp rule: upload only when due AND the "
                        "innovation energy clears the CADA RHS")
    p.add_argument("--local-steps", type=int, default=1,
                   help="delta-payload rules (local_momentum | fedadam): "
                        "local optimizer steps per communication round — "
                        "the payload becomes the accumulated model delta")
    p.add_argument("--adapt-local-steps", action="store_true",
                   help="sim runtime only: adapt each worker's local-step "
                        "count from observed comm vs compute time (avp's "
                        "period rule generalized to local steps)")
    p.add_argument("--local-steps-min", type=int, default=1,
                   help="adaptive local steps: per-worker lower bound")
    p.add_argument("--local-steps-max", type=int, default=0,
                   help="adaptive local steps: upper bound (0 = max-delay)")
    p.add_argument("--local-lr", type=float, default=0.1,
                   help="delta-payload rules: local optimizer step size")
    p.add_argument("--state-fsdp-axes", default="",
                   help="comma list of mesh axes to ZeRO the flat "
                        "optimizer/comm state over (e.g. 'data')")
    p.add_argument("--moments-dtype", default="float32",
                   choices=["float32", "bfloat16"],
                   help="storage dtype of the flat {h, v̂} moment planes")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--c", type=float, default=1.0)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--workers", type=int, default=0,
                   help="0 = mesh data-axis size")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=0)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--trace", default="",
                   help="write a Chrome-trace/Perfetto JSON timeline "
                        "here: sim runtime = every simulated compute/"
                        "transfer/gate event on the simulated clock (one "
                        "track per worker + server); mesh runtime = "
                        "per-step train spans on the wall clock. Open in "
                        "chrome://tracing or ui.perfetto.dev")
    p.add_argument("--metrics-out", default="",
                   help="append the run's summary + per-rule comm ledger "
                        "(uploads, bytes split, staleness histogram, gate "
                        "margins) as one JSONL row to this path")
    p.add_argument("--metrics-prom", default="",
                   help="also write the metrics as a Prometheus "
                        "textfile-collector snapshot to this path")
    return p


def rule_from_args(args) -> CommRule:
    """The communication rule the command line asks for."""
    return CommRule(kind=args.rule, c=args.c, d_max=10, max_delay=50,
                    quantize_bits=args.quantize_bits,
                    error_feedback=not args.no_error_feedback,
                    topk_frac=args.topk_frac,
                    sparse_wire=args.sparse_wire,
                    period_min=args.period_min,
                    period_max=args.period_max,
                    avp_compose=args.avp_compose,
                    local_steps=args.local_steps,
                    adapt_local_steps=args.adapt_local_steps,
                    local_steps_min=args.local_steps_min,
                    local_steps_max=args.local_steps_max,
                    local_lr=args.local_lr,
                    server_lr=args.lr)


def hparams_from_args(rule: CommRule, args) -> TrainHParams:
    """The trainer hyper-parameters the command line asks for."""
    return TrainHParams(rule=rule,
                        lr=args.lr, microbatches=args.microbatches,
                        moments_dtype=args.moments_dtype,
                        state_fsdp_axes=tuple(
                            a for a in args.state_fsdp_axes.split(",") if a))


def run_mesh(cfg, rule, args) -> MeshRun:
    """`--runtime mesh`: train on the host devices — the jitted Algorithm-1
    step (``jit_train_step`` on the host mesh, or the mesh-free vmapped
    step over ``--workers`` simulated workers on one device), with
    checkpointing, per-step logging and the telemetry sinks. The twin of
    :func:`run_sim`; returns the final state, the logged history and the
    jitted step with its first batch (for inspecting the compiled
    program)."""
    hp = hparams_from_args(rule, args)
    if args.workers:
        # simulated workers: the mesh-free step on the default device, over
        # an unsharded flat plane. The state is donated: the loop threads
        # it linearly, so the buffers alias in place every step.
        m, shards, mesh = args.workers, 1, None
        step = jax.jit(make_train_step(cfg, hp, m), donate_argnums=(0,))
    else:
        mesh = make_host_mesh()
        make, sspecs, m = jit_train_step(cfg, mesh, hp)
        # the flat layout pads to the mesh's state-shard count: state init
        # must use the SAME count as the compiled step
        shards = flat_state_shards(cfg, mesh, hp)

    batches = make_token_batches(cfg, global_batch=args.global_batch,
                                 seq=args.seq, steps=args.steps)
    # mesh runtime: delta-payload rules run their FIXED local-step count
    # (adaptive H was rejected above); the global batch carves into
    # H · M per-local-step slices
    h = (rule.local_steps
         if STRATEGIES[rule.kind].delta_payload else 1)
    if args.global_batch % (h * m):
        raise SystemExit(
            f"--global-batch {args.global_batch} must divide into "
            f"local_steps*workers = {h}*{m} per-local-step slices")
    # telemetry: per-step train spans on the wall clock (``dispatch``, the
    # call that enqueues the step; ``wait``, the fetch of its scalars on
    # the steps that log them) + a comm ledger fed from device-side metric
    # buffers fetched every --metrics-every steps (same cadence contract
    # as the cohort driver)
    obs_on = bool(args.trace or args.metrics_out or args.metrics_prom)
    tracer = None
    ledger = None
    obs_buf: list = []
    if obs_on:
        from repro.core.comm import strategy_for
        from repro.obs import CommLedger, Tracer
        tracer = Tracer() if args.trace else None
        ledger = CommLedger.for_strategy(strategy_for(rule))
    from repro.obs.trace import as_tracer
    tr = as_tracer(tracer)

    def drain_obs():
        if ledger is not None and obs_buf:
            for met in jax.device_get(obs_buf):
                ledger.observe_round(met)
            obs_buf.clear()

    first = worker_split({"tokens": batches[0]}, m, local_steps=h)
    with jax.set_mesh(mesh) if mesh else contextlib.nullcontext():
        state = init_train_state(cfg, hp, m, jax.random.PRNGKey(0),
                                 shards=shards)
        if mesh is not None:
            state = place_train_state(state, mesh, sspecs)
            step = make(jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), first))

        t0 = time.time()
        history = []
        for i in range(args.steps):
            t_step = time.time()
            batch = worker_split({"tokens": batches[i]}, m, local_steps=h)
            with tr.span("dispatch", track="train", args={"step": i}):
                state, mets = step(state, batch)
            if obs_on:
                obs_buf.append(mets)
                if len(obs_buf) >= max(1, args.metrics_every):
                    drain_obs()
            if i % args.log_every == 0 or i == args.steps - 1:
                # the scalars, plus the per-worker upload mask as a list
                with tr.span("wait", track="train", args={"step": i}):
                    row = {k: float(v) for k, v in mets.items()
                           if np.ndim(v) == 0}
                    row["upload_mask"] = np.asarray(
                        mets["upload_mask"]).tolist()
                row["step"] = i
                # fetching the scalars waited for the step: step_s is its
                # host-clock time, the first step's compile included
                row["step_s"] = time.time() - t_step
                row["wall_s"] = round(time.time() - t0, 1)
                history.append(row)
                print(f"step {i:5d} loss={row['loss']:.4f} "
                      f"uploads={int(row['uploads'])}/{m} "
                      f"skip={row['skip_rate']:.2f} "
                      f"({row['wall_s']}s)", flush=True)
            if (args.ckpt_every and args.ckpt_dir
                    and i and i % args.ckpt_every == 0):
                ckpt.save(os.path.join(args.ckpt_dir, f"step_{i}"),
                          state.params, step=i)

    if args.ckpt_dir:
        ckpt.save(os.path.join(args.ckpt_dir, f"step_{args.steps}"),
                  state.params, step=args.steps)
        with open(os.path.join(args.ckpt_dir, "history.json"), "w") as f:
            json.dump(history, f, indent=1)
    final = np.mean([h["loss"] for h in history[-3:]])
    print(f"done: final loss {final:.4f}")
    if obs_on:
        drain_obs()
        row = {"runtime": "mesh", "arch": args.arch, "rule": args.rule,
               "steps": args.steps, "final_loss": float(final),
               **ledger.summary()}
        _write_obs(args, tracer, row)
    return MeshRun(state, history, step, first)


def main() -> None:
    args = build_parser().parse_args()
    cfg = (C.get_smoke_config(args.arch) if args.smoke
           else C.get_config(args.arch))
    if not cfg.embed_input:
        raise SystemExit(f"{args.arch} consumes modality embeddings; use "
                         "examples/serve_decode.py or the dry-run for it")
    if args.adapt_local_steps and args.runtime != "sim":
        raise SystemExit(
            "--adapt-local-steps needs --runtime sim: the adaptation "
            "signal is comm vs compute time from the sim's link model — "
            "the mesh runtime has no clock to adapt from")
    init_compile_cache()
    rule = rule_from_args(args)
    if args.runtime == "sim":
        run_sim(cfg, rule, args)
    else:
        run_mesh(cfg, rule, args)


if __name__ == "__main__":
    main()
