"""Multi-pod dry-run: lower + compile every (architecture × input shape ×
mesh) combination against the production meshes and extract the roofline
terms from the compiled artifact.

Run as:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] \
        --out results/dryrun.jsonl

The FIRST TWO LINES below must stay first: jax locks the device count on
first init, and the production meshes need 512 placeholder host devices.
Smoke tests and benches must NOT import this module (they want 1 device).
"""
import os  # noqa: E402  (the two-line contract of the task spec)
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402

from repro import configs as cfgs                              # noqa: E402
from repro.configs.base import SHAPES, adapt_for_shape, input_specs  # noqa: E402
from repro.distributed.serving import (                        # noqa: E402
    jit_decode_step, jit_prefill_step,
)
from repro.distributed.trainer import (                        # noqa: E402
    abstract_train_state, flat_state_shards, jit_train_step,
    worker_split_abstract,
)
from repro.launch.mesh import (                                # noqa: E402
    DCN_BW, HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh,
)
from repro.launch.policy import train_policy                   # noqa: E402
from repro.models.config import active_param_count, param_count  # noqa: E402
from repro.models.model import abstract_params                 # noqa: E402
from repro.utils.hlo_cost import analyze as hlo_analyze        # noqa: E402


def lower_combo(arch: str, shape_name: str, *, multi_pod: bool = False,
                rule_kind: str | None = None, hp_override=None,
                model_par: int = 16, cfg_override=None):
    """Lower one (arch, shape, mesh) combo. Returns (lowered, meta)."""
    cfg = cfg_override or cfgs.get_config(arch)
    shape = SHAPES[shape_name]
    cfg = adapt_for_shape(cfg, shape)
    mesh = make_production_mesh(multi_pod=multi_pod, model_par=model_par)
    aps = abstract_params(cfg)

    if shape.kind == "train":
        hp = hp_override or train_policy(cfg, mesh, rule_kind)
        make, _, m = jit_train_step(cfg, mesh, hp)
        batch_sds = worker_split_abstract(
            input_specs(cfg, shape)["batch"], m)
        # state shapes must match the step's: the flat layout pads to the
        # mesh's state-shard count
        state_sds = abstract_train_state(
            cfg, hp, m, shards=flat_state_shards(cfg, mesh, hp))
        with jax.set_mesh(mesh):
            lowered = make(batch_sds).lower(state_sds, batch_sds)
        meta = {"step": "train_step", "rule": hp.rule.kind,
                "microbatches": hp.microbatches,
                "cada_dtype": hp.cada_dtype,
                "moments_dtype": hp.moments_dtype}
    elif shape.kind == "prefill":
        specs = input_specs(cfg, shape)
        with jax.set_mesh(mesh):
            jitted = jit_prefill_step(cfg, mesh, specs)
            lowered = jitted.lower(aps, specs)
        meta = {"step": "prefill"}
    else:  # decode
        specs = input_specs(cfg, shape)
        with jax.set_mesh(mesh):
            jitted, cache_sds, inputs_sds = jit_decode_step(
                cfg, mesh, shape.batch, shape.seq)
            lowered = jitted.lower(aps, cache_sds, inputs_sds)
        meta = {"step": "serve_step",
                "sliding_window": cfg.sliding_window}

    meta.update(arch=arch, shape=shape_name,
                mesh="2x16x16" if multi_pod else "16x16",
                chips=512 if multi_pod else 256)
    return lowered, cfg, shape, meta


def roofline_terms(compiled, lowered, cfg, shape, meta) -> dict:
    """The three roofline terms, per chip, from the compiled artifact.

    XLA's flat cost_analysis counts while bodies once; we re-derive flops /
    bytes / collective traffic with the trip-count-aware analyzer
    (utils/hlo_cost.py) over the post-optimization per-device HLO.
    """
    cost = hlo_analyze(compiled.as_text())
    flops = float(cost.flops)
    bytes_acc = float(cost.bytes_fused)   # TPU-fused estimate (see hlo_cost)

    t_compute = flops / PEAK_FLOPS_BF16
    t_memory = bytes_acc / HBM_BW
    t_coll = cost.collective_bytes / ICI_BW
    t_dcn = cost.dcn_bytes / DCN_BW
    dominant = max((("compute", t_compute), ("memory", t_memory),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]

    n_active = active_param_count(cfg)
    if shape.kind == "train":
        tokens = shape.batch * shape.seq
        model_flops = 6 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.batch * shape.seq
        model_flops = 2 * n_active * tokens
    else:
        tokens = shape.batch
        model_flops = 2 * n_active * tokens
    model_flops_per_chip = model_flops / meta["chips"]
    useful = model_flops_per_chip / flops if flops else 0.0

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                mem[k] = int(v)
    except Exception as e:  # CPU backend may not expose it
        mem["error"] = str(e)

    return {
        **meta,
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": bytes_acc,
        "hlo_bytes_unfused_per_chip": float(cost.bytes_accessed),
        "collective_bytes_per_chip": cost.collective_bytes,
        "dcn_bytes_per_chip": cost.dcn_bytes,
        "t_dcn_s": t_dcn,
        "collectives": dict(cost.coll_count),
        "collective_bytes_by_kind": dict(cost.coll_by_kind),
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops_per_chip": model_flops_per_chip,
        "useful_flops_ratio": useful,
        "params": param_count(cfg),
        "active_params": n_active,
        "memory_analysis": mem,
    }


def run_combo(arch: str, shape_name: str, *, multi_pod: bool = False,
              rule_kind: str | None = None, verbose: bool = True,
              hp_override=None, model_par: int = 16, cfg_override=None,
              tag: str = "") -> dict:
    t0 = time.time()
    lowered, cfg, shape, meta = lower_combo(
        arch, shape_name, multi_pod=multi_pod, rule_kind=rule_kind,
        hp_override=hp_override, model_par=model_par,
        cfg_override=cfg_override)
    if model_par != 16:
        meta["mesh"] = meta["mesh"].replace(
            "16x16", f"{256 // model_par}x{model_par}")
    if tag:
        meta["tag"] = tag
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    row = roofline_terms(compiled, lowered, cfg, shape, meta)
    row["t_lower_s"] = round(t_lower, 1)
    row["t_compile_s"] = round(t_compile, 1)
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {row['mesh']} "
              f"({row['step']}): OK  "
              f"compute={row['t_compute_s']:.3e}s "
              f"memory={row['t_memory_s']:.3e}s "
              f"collective={row['t_collective_s']:.3e}s "
              f"dominant={row['dominant']} "
              f"useful={row['useful_flops_ratio']:.2f} "
              f"(lower {t_lower:.0f}s, compile {t_compile:.0f}s)",
              flush=True)
        if row["memory_analysis"]:
            print(f"         memory_analysis: {row['memory_analysis']}",
                  flush=True)
    return row


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default=None, help="architecture id")
    p.add_argument("--shape", default=None, choices=list(SHAPES))
    p.add_argument("--all", action="store_true",
                   help="every (arch × shape) combination")
    p.add_argument("--multi-pod", action="store_true",
                   help="2x16x16 (512-chip) mesh instead of 16x16")
    p.add_argument("--rule", default=None,
                   choices=["cada1", "cada2", "lag", "always"])
    p.add_argument("--model-par", type=int, default=16,
                   help="model-axis size (256/model_par becomes data)")
    p.add_argument("--set", dest="overrides", action="append", default=[],
                   help="config override field=value (repeatable; §Perf)")
    p.add_argument("--hp-set", dest="hp_overrides", action="append",
                   default=[],
                   help="TrainHParams override field=value (repeatable)")
    p.add_argument("--out", default=None, help="append JSONL rows here")
    args = p.parse_args()

    def cfg_override_for(arch):
        if not args.overrides:
            return None
        cfg = cfgs.get_config(arch)
        kw = {}
        for ov in args.overrides:
            key, val = ov.split("=", 1)
            for cast in (int, float):
                try:
                    val = cast(val)
                    break
                except ValueError:
                    continue
            if val in ("True", "False"):
                val = val == "True"
            kw[key] = val
        return cfg.with_(**kw)

    combos = []
    if args.all:
        for arch in cfgs.list_archs():
            for shape in SHAPES:
                combos.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]

    failures = []
    for arch, shape in combos:
        try:
            hp_override = None
            if args.hp_overrides:
                import dataclasses
                from repro.launch.policy import train_policy as _tp
                cfg0 = cfgs.get_config(arch)
                mesh0 = make_production_mesh(multi_pod=args.multi_pod,
                                             model_par=args.model_par)
                hp_override = _tp(cfg0, mesh0, args.rule)
                kw = {}
                for ov in args.hp_overrides:
                    key, val = ov.split("=", 1)
                    if key.endswith("_axes"):
                        val = tuple(a for a in val.split(",") if a)
                    else:
                        for cast in (int, float):
                            try:
                                val = cast(val)
                                break
                            except ValueError:
                                continue
                        if val in ("True", "False"):
                            val = val == "True"
                    kw[key] = val
                hp_override = dataclasses.replace(hp_override, **kw)
            row = run_combo(arch, shape, multi_pod=args.multi_pod,
                            rule_kind=args.rule, model_par=args.model_par,
                            cfg_override=cfg_override_for(arch),
                            hp_override=hp_override,
                            tag=";".join(args.overrides
                                         + args.hp_overrides))
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
        except Exception:
            failures.append((arch, shape))
            print(f"[dryrun] {arch} × {shape}: FAILED", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} combos failed: {failures}")
    print(f"[dryrun] all {len(combos)} combos passed", flush=True)


if __name__ == "__main__":
    main()
