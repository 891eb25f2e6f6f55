"""Smoke run of the CADA trainer on a TPU: the main path, end to end.

    python chip_smoke.py               # one chip: phases A and B
    python chip_smoke.py --four-chips  # four chips: the sharded M=4 step only

Phase A trains stablelm-1.6b's one-chip cut (``chip_config`` in
``configs/stablelm_1_6b.py``: every published width, the vocabulary and the
depth cut to one chip's share) with rule cada2 and M=4 simulated workers,
through ``launch/train.run_mesh`` as the launcher runs it. It checks that
the losses are finite, that the compiled step holds the Pallas kernels, that
those kernels agree with ``kernels/ref.py`` and the jnp forms at the real
flat width, and that the first loss agrees with a float32 reference.

Phase B runs the pipelined cohort driver (``CADAEngine.run_cohort``, cada2,
M=10^4 workers, cohorts of C=64, the 22-64-2 MLP) and checks that it is
bit-exact to the serial driver (``pipeline=False``).

``--four-chips`` runs ``jit_train_step`` on the host mesh (4, 1), one worker
per chip on the ``data`` axis with the flat state ZeRO-sharded over it, and
compares it with the same M=4 step unsharded on one chip of that host.

Any failed check exits non-zero, and so does a host where JAX finds no TPU:
there is no CPU fallback. Timings printed here are one smoke run each, not
a benchmark. The last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

STEPS = 4             # phase A / four-chip steps
GLOBAL_BATCH = 8      # 2 sequences per worker
SEQ = 1024
# Loss tolerances, absolute, on losses near ln(12544) + 0.4 ≈ 9.8. The
# model computes in bf16, whose unit roundoff is 2^-8 ≈ 3.9e-3 relative;
# 4e-3 absolute is 4e-4 relative, ten times under one bf16 rounding of the
# loss, and about six times the bf16-vs-float32 gap of this cut measured on
# the CPU (6.4e-4). Losing bf16 to a coarser format would exceed it.
LOSS_ATOL = 4e-3
# Elementwise outputs of the fused update: both sides evaluate the same
# fp32 expression; they may differ by FMA contraction and by how divide and
# sqrt are lowered, a few fp32 ulps (2^-23 ≈ 1.2e-7). 1e-5 relative is ~80
# ulps, and 2500 times under bf16's spacing, so a kernel that rounds
# through bf16 fails it.
ELEM_RTOL, ELEM_ATOL = 1e-5, 1e-8
# Squared norms over ~1.5e8 fp32 terms summed in different orders (the
# kernels add per-block sums sequentially, XLA reduces as a tree): the
# random-walk error is ~sqrt(n_blocks)·2^-24 ≈ 4e-6; 1e-4 leaves 25x room
# and is 40 times under bf16's 3.9e-3.
NORM_RTOL = 1e-4


def require_tpu():
    """Exit non-zero unless JAX's first device is a TPU."""
    try:
        devs = jax.devices()
    except RuntimeError as e:
        sys.exit(f"chip_smoke: JAX found no devices: {e}")
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}")
    print(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", flush=True)
    return devs


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")
    print(f"  ok: {what}", flush=True)


def train_args(*extra):
    from repro.launch.train import build_parser
    return build_parser().parse_args(
        ["--arch", "stablelm-1.6b", "--rule", "cada2", "--steps", str(STEPS),
         "--global-batch", str(GLOBAL_BATCH), "--seq", str(SEQ),
         "--log-every", "1", *extra])


def report_run(label: str, run) -> list:
    steps = [r["step_s"] for r in run.history]
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"[one smoke run, not a benchmark] {label}: first step "
          f"(compile + run) {steps[0]:.3f} s, later steps "
          f"{', '.join(f'{s:.4f}' for s in steps[1:])} s, device 0 "
          f"peak_bytes_in_use {peak}", flush=True)
    return [r["loss"] for r in run.history]


def rel_err(got, want, rtol, atol):
    """max |got − want| / (atol + rtol·|want|), on the device."""
    got, want = (jnp.asarray(x, jnp.float32) for x in (got, want))
    return float(jnp.max(jnp.abs(got - want) / (atol + rtol * jnp.abs(want))))


# ------------------------------------------------------------ phase A

def phase_a() -> None:
    from repro.configs.stablelm_1_6b import chip_config
    from repro.kernels import ops as kops
    from repro.launch.train import rule_from_args, run_mesh

    cfg = chip_config()
    print(f"phase A: {cfg.name} L={cfg.n_layers} d={cfg.d_model} "
          f"V={cfg.vocab} (reduced {dict(cfg.reduced)}), cada2, M=4, "
          f"batch {GLOBAL_BATCH}x{SEQ}", flush=True)
    check(kops._use_pallas(None) == (True, False),
          "flat ops route to the compiled Pallas kernels")
    args = train_args("--workers", "4")
    run = run_mesh(cfg, rule_from_args(args), args)
    losses = report_run("phase A cada2 M=4", run)
    check(bool(np.isfinite(losses).all()), f"losses finite: {losses}")

    compiled = run.step.lower(run.state, run.batch).compile()
    ma = compiled.memory_analysis()
    print(f"compiled step memory_analysis: arguments "
          f"{ma.argument_size_in_bytes} B, temporaries "
          f"{ma.temp_size_in_bytes} B, outputs {ma.output_size_in_bytes} B "
          f"({ma.alias_size_in_bytes} B aliased)", flush=True)
    hlo = compiled.as_text()
    for op in ("fused_amsgrad_flat", "batched_diff_sq_norm"):
        check(any("tpu_custom_call" in line
                  and f"jit({op})/pallas_call" in line
                  for line in hlo.splitlines()),
              f"compiled step runs the {op} kernel as a tpu_custom_call")
    batch = run.batch
    del run, compiled, hlo
    gc.collect()

    check_loss_vs_float32(cfg, batch, losses[0])
    check_kernels(cfg)


def check_loss_vs_float32(cfg, batch, loss0: float) -> None:
    """The first step's loss (θ⁰, batch 0, mean over the workers) against
    ``lm_loss`` in float32 at the highest matmul precision."""
    from repro.models.model import init_params, lm_loss
    cfg32 = cfg.with_(dtype="float32")
    params = jax.tree.map(lambda p: p.astype(jnp.float32),
                          init_params(cfg, jax.random.PRNGKey(0)))
    loss_fn = jax.jit(lambda p, b: lm_loss(cfg32, p, b)[0])
    m = batch["tokens"].shape[0]
    with jax.default_matmul_precision("highest"):
        ref = float(np.mean([loss_fn(params, {"tokens": batch["tokens"][w]})
                             for w in range(m)]))
    del params
    check(abs(loss0 - ref) <= LOSS_ATOL,
          f"step-0 loss {loss0:.6f} vs float32 reference {ref:.6f} "
          f"(|diff| {abs(loss0 - ref):.2e} <= {LOSS_ATOL})")


def check_kernels(cfg) -> None:
    """Pallas vs ``kernels/ref.py`` and the jnp forms at the real n_flat."""
    from repro.distributed.trainer import flat_layout
    from repro.kernels import ops as kops
    from repro.kernels import ref

    n = flat_layout(cfg).n_flat
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    theta = jax.random.normal(k[0], (n,), jnp.float32)
    h = 0.1 * jax.random.normal(k[1], (n,), jnp.float32)
    vhat = jnp.abs(0.01 * jax.random.normal(k[2], (n,), jnp.float32))
    g = jax.random.normal(k[3], (n,), jnp.float32)
    got = kops.fused_amsgrad_flat(theta, h, vhat, g, 3e-4, interpret=False)
    # ref.amsgrad_ref is also the jnp form ops.fused_amsgrad_flat takes off
    # the TPU
    want = jax.jit(ref.amsgrad_ref)(theta, h, vhat, g, 3e-4)
    for name, a, b in zip(("theta", "h", "vhat"), got, want):
        e = rel_err(a, b, ELEM_RTOL, ELEM_ATOL)
        check(e <= 1.0, f"fused_amsgrad_flat {name}' at n_flat={n}: "
                        f"max err {e:.3g} of tolerance (rtol {ELEM_RTOL})")
    e = rel_err(got[3], want[3], NORM_RTOL, 0.0)
    check(e <= 1.0, f"fused_amsgrad_flat ||dtheta||^2 {float(got[3]):.7g} vs "
                    f"{float(want[3]):.7g}: {e:.3g} of tolerance")
    del theta, h, vhat, g, got, want
    gc.collect()

    m = 4
    a = jax.random.normal(k[0], (m, n), jnp.float32)
    b = jax.random.normal(k[1], (m, n), jnp.float32)
    got = kops.batched_diff_sq_norm(a, b, interpret=False)
    want_ref = jax.jit(jax.vmap(ref.diff_sq_norm_ref))(a, b)
    want_jnp = jax.jit(lambda x, y: jnp.sum(jnp.square(x - y), axis=1))(a, b)
    for label, want in (("kernels/ref.py", want_ref), ("jnp", want_jnp)):
        e = rel_err(got, want, NORM_RTOL, 0.0)
        check(e <= 1.0, f"batched_diff_sq_norm (M={m}, n_flat={n}) vs "
                        f"{label}: {e:.3g} of tolerance")


# ------------------------------------------------------------ phase B

def phase_b() -> None:
    from repro.core.engine import (CADAEngine, make_cohort_sampler,
                                   sample_cohorts)
    from repro.core.rules import CommRule
    from repro.data.partition import pad_to_matrix, uniform_partition
    from repro.data.synthetic import ijcnn1_like
    from repro.models.small import mlp_init, mlp_loss
    from repro.optim.fused import FusedAMSGrad

    m, c, rounds = 10_000, 64, 6
    print(f"phase B: cohort driver, cada2, M={m}, C={c}, {rounds} rounds, "
          "22-64-2 MLP", flush=True)
    rule = CommRule(kind="cada2", c=0.6, d_max=10, max_delay=100)
    ds = ijcnn1_like(n=20_000)
    mtx = pad_to_matrix(uniform_partition(ds.n, m, seed=0))
    sample = make_cohort_sampler(ds.x, ds.y, mtx, 32)
    params = mlp_init(jax.random.PRNGKey(7), 22, 64, 2)
    cohorts = sample_cohorts(m, c, rounds, seed=0)

    def batch_fn(i, cohort):
        return sample(jax.random.PRNGKey(200 + i), jnp.asarray(cohort))

    runs = {}
    for pipeline in (True, False):
        eng = CADAEngine(mlp_loss, FusedAMSGrad(lr=0.05), rule, m)
        st, pool = eng.init_cohort(params)
        t = time.time()
        st, mets = eng.run_cohort(st, pool, batch_fn, cohorts,
                                  pipeline=pipeline, metrics_every=4)
        jax.block_until_ready(st)
        print(f"[one smoke run, not a benchmark] phase B pipeline="
              f"{pipeline}: {rounds} rounds in {time.time() - t:.3f} s "
              "(compile included)", flush=True)
        runs[pipeline] = (st, pool, mets)

    (st_p, pool_p, mets_p), (st_s, pool_s, mets_s) = runs[True], runs[False]
    losses = np.asarray([mm["loss"] for mm in mets_p])
    check(len(mets_p) == rounds and bool(np.isfinite(losses).all()),
          f"{rounds} rounds, losses finite: {losses.tolist()}")
    check(int(np.asarray(mets_p[0]["uploads"])) == c,
          "round 0 force-uploads its cohort")
    check(len(mets_p) == len(mets_s)
          and all(np.array_equal(np.asarray(mp[k]), np.asarray(ms[k]))
                  for mp, ms in zip(mets_p, mets_s) for k in mp),
          "pipelined metrics bit-exact to the serial driver")
    check(all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
              zip(jax.tree.leaves(st_p), jax.tree.leaves(st_s))),
          "pipelined engine state bit-exact to the serial driver")
    check(all(np.array_equal(np.asarray(pool_p.planes[k]),
                             np.asarray(pool_s.planes[k]))
              for k in pool_s.planes),
          "pipelined worker pool bit-exact to the serial driver")


# ------------------------------------------------------- four chips

def four_chips() -> None:
    from repro.configs.stablelm_1_6b import chip_config
    from repro.launch.train import rule_from_args, run_mesh

    check(len(jax.devices()) == 4, "four devices")
    cfg = chip_config()
    print(f"four chips: {cfg.name}, cada2, M=4 on the data axis of the "
          "(4, 1) host mesh, state sharded over data", flush=True)
    sharded_args = train_args("--state-fsdp-axes", "data")
    one_args = train_args("--workers", "4")
    # compile the one-device step into the persistent cache while the
    # sharded run compiles, so that its own run finds it there
    with ThreadPoolExecutor(1) as pool:
        warm = pool.submit(compile_mesh_free_step, cfg, one_args)
        run = run_mesh(cfg, rule_from_args(sharded_args), sharded_args)
        warm.result()
    report_run("four-chip host, sharded", run)
    h = run.state.h
    check(tuple(h.sharding.spec) == ("data",)
          and len(h.sharding.device_set) == 4,
          "server planes shard over the 4 chips")
    runs = {"sharded": run.history}
    del run, h
    gc.collect()
    run = run_mesh(cfg, rule_from_args(one_args), one_args)
    report_run("four-chip host, one device", run)
    runs["one device"] = run.history
    del run
    (ls, ms), (l1, m1) = ([[r[k] for r in hist] for k in ("loss", "upload_mask")]
                          for hist in (runs["sharded"], runs["one device"]))
    check(bool(np.isfinite(ls).all()), f"sharded losses finite: {ls}")
    diff = float(np.max(np.abs(np.subtract(ls, l1))))
    check(diff <= LOSS_ATOL, f"losses sharded {ls} vs one device {l1}: "
                             f"max |diff| {diff:.2e} <= {LOSS_ATOL}")
    check(ms == m1, f"upload masks equal over {STEPS} steps: {ms}")


def compile_mesh_free_step(cfg, args) -> None:
    """Compile the step ``run_mesh`` runs for ``--workers M``, from shapes."""
    from repro.distributed.trainer import abstract_train_state, make_train_step
    from repro.launch.train import hparams_from_args, rule_from_args
    m = args.workers
    hp = hparams_from_args(rule_from_args(args), args)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (m, args.global_batch // m, args.seq + 1), jnp.int32)}
    jax.jit(make_train_step(cfg, hp, m), donate_argnums=(0,)).lower(
        abstract_train_state(cfg, hp, m), batch).compile()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded M=4 step on four chips and "
                         "its one-device comparison")
    opts = ap.parse_args()
    devs = require_tpu()
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.cache import init_compile_cache
    print(f"compile cache: {init_compile_cache()}", flush=True)
    if opts.four_chips:
        four_chips()
    else:
        phase_a()
        gc.collect()
        phase_b()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
