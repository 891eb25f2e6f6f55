"""CPU tests of the benchmark's yardstick: trace reduction, operation and
byte counts, the token law, and the loading of every cell by name."""
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import compare, feed, flops, spec, traces  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "trace_small.json.gz"
# four steps of the four-chip cell's window, traced on four v5e chips
RECORDED_4 = RECORDED.with_name("trace_dp4.json.gz")


# ------------------------------------------------------------------ traces

def _ev(name, start, dur, **stats):
    return [name, float(start), float(dur), stats]


SYNTH = {
    "devices": {
        "/device:TPU:0": [
            _ev("fusion.1", 100, 50),
            _ev("fusion.2", 140, 30),                 # overlaps fusion.1
            _ev("all-reduce.3", 160, 40, op="all-reduce"),  # 170..200 bare
            _ev("fused_amsgrad_flat.4", 220, 20, op="custom-call"),
            _ev("copy-start.9", 240, 1, op="copy-start"),  # reads its output
            _ev("fusion.5", 280, 100),                # crosses the window end
        ],
        "/device:TPU:1": [
            _ev("fusion.1", 110, 90),
            _ev("all-reduce.3", 150, 30, op="all-reduce"),  # all hidden
            _ev("fused_amsgrad_flat.4", 230, 20, op="custom-call"),
            _ev("while.8", 100, 100, op="while"),     # holds fusion.1
        ],
    },
    "host": [
        _ev("bench.window", 100, 200),
        _ev("bench.prep", 200, 25),
        _ev("bench.wait", 240, 60),
    ],
}


def test_busy_union_and_idle_share():
    s = traces.summarize(SYNTH)
    # dev0 busy in [100,300]: 100..200, 220..241, 280..300 -> 141
    # dev1: 100..200 (the while op), 230..250 -> 120
    assert s.window_s == pytest.approx(200e-9)
    assert s.busy_s == pytest.approx((141 + 120) / 2 * 1e-9)


def test_kernel_time_by_name_path():
    secs, calls = traces.op_calls(SYNTH, r"fused_amsgrad_flat(\.\d+)?$")
    assert calls == 2 and secs == pytest.approx(40e-9)
    assert traces.op_calls(SYNTH, r"batched_diff_sq_norm") == (0.0, 0)


def test_instruction_names_and_opcodes():
    text = ('%fused_amsgrad_flat.1 = (f32[8,128]{1,0:T(8,128)}, f32[1,1]) '
            'custom-call(f32[8,128]{1,0:T(8,128)} %pad.3), custom_call_'
            'target="tpu_custom_call"')
    assert traces.split_instruction(text) == ("fused_amsgrad_flat.1",
                                              "custom-call")
    text = ('%while.451 = (s32[]{:T(128)}, bf16[2]{0}) while((s32[]{:T(128)}'
            ', bf16[2]{0}) %tuple.3), condition=%c, body=%b')
    assert traces.split_instruction(text) == ("while.451", "while")
    assert traces.split_instruction("jit_step(7)") == ("jit_step(7)", "")


def test_exposed_collective_time():
    # dev0: all-reduce 160..200 minus fusions up to 170 -> 30; dev1: 0
    assert traces.exposed_collective_s(SYNTH) == pytest.approx(15e-9)


def _traced_view(trace, steps):
    cell = spec.load_cell("stablelm2-chip.cada2-dp4")
    return type("V", (), {"trace": trace, "steps": steps,
                          "summary": traces.summarize(trace)})(), cell


def test_exposed_collective_ms_reads_per_step():
    view, cell = _traced_view(SYNTH, 3)
    read = spec.metric_reader(cell, "exposed_collective_ms")
    assert read(view) == pytest.approx(15e-9 / 3 * 1e3)


def test_exposed_collective_ms_is_null_without_a_collective():
    bare = {"devices": {d: [e for e in evs if not traces.is_collective(e)]
                        for d, evs in SYNTH["devices"].items()},
            "host": SYNTH["host"]}
    view, cell = _traced_view(bare, 3)
    assert spec.metric_reader(cell, "exposed_collective_ms")(view) is None


def test_gap_attribution_and_top_ops():
    s = traces.summarize(SYNTH)
    # dev0 gaps: 200..220 (prep), 241..280 (wait); dev1 (the while op
    # covers 100..200): 200..230 (prep 200..225), 250..300 (wait)
    assert s.idle_gaps[0] == ["bench.wait", pytest.approx(50e-9)]
    assert ["bench.wait", pytest.approx(39e-9)] in s.idle_gaps
    assert ["bench.prep", pytest.approx(30e-9)] in s.idle_gaps
    names = [n for n, _ in s.device_ops]
    assert names[0] == "fusion.1"            # (100 + 90) / 2 per device
    assert "while.8" not in names            # its body is counted once


def test_interval_helpers():
    assert traces.merge([(5, 7), (1, 3), (2, 4)]) == [[1, 4], [5, 7]]
    assert traces.subtract([(0, 10)], [[2, 3], [5, 12]]) == [(0, 2), (3, 5)]
    assert traces.gaps([[2, 3]], 0, 4) == [(0, 2), (3, 4)]


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trace_reduces():
    tr = traces.load_plain(str(RECORDED))
    s = traces.summarize(tr)
    assert 0 < s.busy_s <= s.window_s
    steps = sum(1 for e in tr["host"] if e[0] == "bench.dispatch")
    for pattern in (r"fused_amsgrad_flat(\.\d+)?$",
                    r"batched_diff_sq_norm(\.\d+)?$"):
        secs, calls = traces.op_calls(tr, pattern)
        assert calls == steps and 0 < secs < s.window_s
    assert all(name.startswith("bench.") or name == "none"
               for name, _ in s.idle_gaps)


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_one_chip_trace_reads_no_collective():
    tr = traces.load_plain(str(RECORDED))
    steps = sum(1 for e in tr["host"] if e[0] == "bench.dispatch")
    view, cell = _traced_view(tr, steps)
    assert spec.metric_reader(cell, "exposed_collective_ms")(view) is None


def test_recorded_four_chip_trace_reduces():
    tr = traces.load_plain(str(RECORDED_4))
    assert len(tr["devices"]) == 4
    s = traces.summarize(tr)
    assert 0 < s.busy_s <= s.window_s
    steps = sum(1 for e in tr["host"] if e[0] == "bench.dispatch")
    for pattern in (r"fused_amsgrad_flat(\.\d+)?$",
                    r"batched_diff_sq_norm(\.\d+)?$"):
        secs, calls = traces.op_calls(tr, pattern)
        assert calls == 4 * steps and 0 < secs < 4 * s.window_s
    view, cell = _traced_view(tr, steps)
    exposed = spec.metric_reader(cell, "exposed_collective_ms")(view)
    assert 0 < exposed < s.window_s / steps * 1e3


# ------------------------------------------------------------------ counts

def _cfg(name):
    return spec.load_cell({"stablelm-2-1.6b-chip": "stablelm2-chip.cada2-m4",
                           "internlm2-1.8b-chip": "internlm2-chip.cada2-m4"}
                          [name]).config


def test_param_count_hand_counts():
    s = _cfg("stablelm-2-1.6b-chip")
    # per layer: attention 4·2048² + SwiGLU 3·2048·5632 + two norms
    per = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 2 * 2048
    assert flops.param_count(s) == 2 * 12544 * 2048 + 2048 + 2 * per
    assert flops.n_flat(s) == 154_150_912            # the chip cut's n_flat
    i = _cfg("internlm2-1.8b-chip")
    per = (2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 8192 + 2 * 2048)
    assert flops.param_count(i) == 2 * 11568 * 2048 + 2048 + per


def test_param_count_matches_the_program():
    from repro.models.config import param_count
    from bench.harness import program_config
    for name in ("stablelm-2-1.6b-chip", "internlm2-1.8b-chip"):
        cfg = _cfg(name)
        assert flops.param_count(cfg) == param_count(program_config(cfg))


def test_model_flops_hand_counts():
    s = _cfg("stablelm-2-1.6b-chip")
    n_mm = 2 * (4 * 2048 ** 2 + 3 * 2048 * 5632) + 2048 * 12544
    assert flops.model_flops_per_token(s, 1024) == 6 * n_mm + 12 * 2 * 2048 * 1024
    assert flops.model_flops_per_token(s, 1024) == pytest.approx(0.8209e9, rel=1e-3)
    i = _cfg("internlm2-1.8b-chip")
    n_mm = (2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192) \
        + 2048 * 11568
    assert flops.model_flops_per_token(i, 1024) == 6 * n_mm + 12 * 16 * 128 * 1024


def test_kernel_bytes_hand_counts():
    s = _cfg("stablelm-2-1.6b-chip")
    n = 154_150_912
    assert flops.amsgrad_bytes_per_chip(s, 1) == 28 * n
    assert flops.lhs_bytes_per_chip(s, 4, 1, 1) == 2 * 4 * 4 * n
    n4 = flops.n_flat(s, 4)
    assert n4 % 32 == 0
    assert flops.amsgrad_bytes_per_chip(s, 4) == 28 * n4 // 4
    assert flops.lhs_bytes_per_chip(s, 4, 4, 4) == 2 * 4 * n4


# -------------------------------------------------------------------- feed

def test_truncated_zipf_stays_in_slice_without_pile_up():
    rng = np.random.default_rng(0)
    vocab = 12544
    ids = feed.truncated_zipf(rng, 1.2, vocab, 400_000)
    assert ids.min() >= 0 and ids.max() < vocab
    counts = np.bincount(ids, minlength=vocab)
    # clipping would put ~13.6% of all ids on vocab-1; truncation puts
    # about p(r) = r^-1.2 / H, under 1e-5 of them there
    assert counts[-1] / ids.size < 1e-4
    # rank-frequency keeps the law: id 0 over id 1 is 2^1.2
    assert counts[0] / counts[1] == pytest.approx(2 ** 1.2, rel=0.05)


def test_step_tokens_are_a_function_of_seed_and_step():
    t = {"workers": 4, "seqs_per_worker": 2, "seq": 16, "zipf_a": 1.2}
    a = feed.step_tokens(t, 100, 2 ** 31 + 5, 3)
    assert a.shape == (8, 17) and a.dtype == np.int32
    assert np.array_equal(a, feed.step_tokens(t, 100, 2 ** 31 + 5, 3))
    assert not np.array_equal(a, feed.step_tokens(t, 100, 2 ** 31 + 5, 4))
    assert not np.array_equal(a, feed.step_tokens(t, 100, 5, 3))


# -------------------------------------------------------------------- spec

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [w["name"] for w in BENCHMARK["workloads"]])
def test_every_workload_loads_by_name(name):
    cell = spec.load_cell(name)
    assert cell.chips in (1, 4)
    assert cell.traffic["chips"] == cell.chips
    assert cell.global_batch % cell.workers == 0
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "tokens_per_s"}
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert callable(spec.metric_reader(cell, m["name"]))
    assert hasattr(spec.reference_model(cell), "loss")
    assert cell.limits["check_steps"] >= 3
    assert set(cell.limits["limits"]) <= set(compare.ORDER)


def test_config_files_are_the_registry_cuts():
    from repro.configs.stablelm_1_6b import chip_config
    import repro.configs as C
    from bench.harness import program_config
    cfg = _cfg("stablelm-2-1.6b-chip")
    assert program_config(cfg) == chip_config().with_(source=cfg["source"])
    pub = C.get_config("internlm2-1.8b")
    got = program_config(_cfg("internlm2-1.8b-chip"))
    for f in ("d_model", "n_heads", "n_kv_heads", "d_ff", "mlp_act",
              "rope_theta", "rotary_pct", "norm_eps", "dtype"):
        assert getattr(got, f) == getattr(pub, f), f
    assert (got.n_layers, got.vocab) == (1, pub.vocab // 8)
    assert dict(got.reduced) == {"n_layers": 24, "vocab": 92544}


def test_config_reduced_lists_every_changed_key():
    """``reduced`` names every cut and every departure from the source."""
    for c in BENCHMARK["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        changed = [k for k, _ in cfg["reduced"]] + list(
            cfg.get("departures", {}))
        assert sorted(changed) == sorted(c["reduced"])


def test_program_runs_one_worker_per_chip():
    import dataclasses
    from bench.harness import Program
    cell = spec.load_cell("stablelm2-chip.cada2-dp4")
    assert cell.chips == cell.workers == 4
    two = dataclasses.replace(cell, traffic={**cell.traffic, "workers": 2})
    with pytest.raises(ValueError, match="one worker per chip"):
        Program(two, [])


def test_unknown_peaks_are_an_error():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A throwaway configuration, traffic mix and per-layer metric, added
    as files and entries, load and read without touching any file."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = json.loads((bench / "configs" / "stablelm-2-1.6b-chip.json")
                     .read_text())
    cfg.update(name="toy", n_layers=1)
    (bench / "configs" / "toy.json").write_text(json.dumps(cfg))
    tr = json.loads((bench / "traffic" / "always-m4.json").read_text())
    tr.update(seq=64)
    (bench / "traffic" / "toy-mix.json").write_text(json.dumps(tr))
    (bench / "metrics" / "toy_count.py").write_text(
        "def read(view):\n    return float(view.steps)\n")
    b = json.loads(json.dumps(BENCHMARK))
    b["configs"].append({"name": "toy", "source": "x", "file":
                         "bench/configs/toy.json", "reduced": [], "why": "t"})
    b["workloads"].append({"name": "toy.mix", "config": "toy",
                           "traffic": "toy-mix", "chips": 1, "why": "t"})
    b["per_layer"].append({"name": "toy_count", "unit": "steps",
                           "better": "higher", "source": "host_clock",
                           "layer": "toy", "moves": "tokens_per_s",
                           "workloads": ["toy.mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.load_cell("toy.mix", root=tmp_path)
    assert cell.config["n_layers"] == 1 and cell.seq == 64
    assert [m["name"] for m in cell.per_layer if m["name"] == "toy_count"]
    view = type("V", (), {"steps": 7})()
    assert spec.metric_reader(cell, "toy_count")(view) == 7.0
    with pytest.raises(KeyError):
        spec.load_cell("no.such.cell", root=tmp_path)
