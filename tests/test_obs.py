"""The unified telemetry plane (repro.obs).

Three contracts under test:

  * **no-op fast path** — ``NULL`` is falsy, allocation-free, and every
    method is a no-op (the <2% disabled-overhead contract's code path);
  * **timeline validity** — the Chrome-trace export of a traced run (the
    discrete-event sim on its simulated clock, the cohort pipeline on
    wall clock) passes the schema validator: per-worker + server tracks
    for the sim, per-round gather/step/scatter(/patch) spans for the
    pipeline;
  * **ledger parity** — for every registered grad rule and both
    delta-payload rules, :class:`repro.obs.metrics.CommLedger` totals
    are bit-equal to the engine's own property-pinned
    ``bytes_per_upload`` accounting (it sums the same fp32 round values
    in the same order).

Plus: the ``metrics_out`` drain-on-error contract (an interrupted cohort
run keeps every completed round's metrics), the registry sinks, and the
traced M=10⁴ cohort smoke the CI ``obs-smoke`` leg runs under the 6 GiB
cap.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.comm import strategy_for
from repro.core.engine import CADAEngine, make_cohort_sampler, sample_cohorts
from repro.core.rules import RULES, CommRule
from repro.data.partition import pad_to_matrix, uniform_partition
from repro.data.synthetic import ijcnn1_like
from repro.models.small import logreg_init, logreg_loss, mlp_init, mlp_loss
from repro.obs import (NULL, CommLedger, MetricsRegistry, NullTracer, Tracer,
                       as_tracer, to_chrome_trace, validate_chrome_trace,
                       write_chrome_trace)
from repro.optim.fused import FusedAMSGrad
from repro.sim import simulate

from tests.test_cohort_pipeline import (ARMS, C, M, STEPS, _cohort_run,
                                        _problem)


# ------------------------------------------------------- no-op fast path

def test_null_tracer_is_falsy_noop():
    """``if tracer:`` guards must skip work; every NULL method no-ops and
    the span context manager is one reusable object (no allocation)."""
    assert not NULL
    assert NULL.enabled is False
    assert isinstance(NULL, NullTracer)
    s1 = NULL.span("a", track="t", args={"k": 1})
    s2 = NULL.span("b")
    assert s1 is s2                       # reusable singleton, no alloc
    with s1:
        pass
    NULL.add_span("x", 0.0, 1.0, track="t")
    NULL.instant("x", 0.5)
    NULL.counter("x", 0.5, 3.0)
    assert NULL.aggregate() == {}
    assert NULL.aggregate("t") == {}


def test_as_tracer_normalizes():
    assert as_tracer(None) is NULL
    tr = Tracer()
    assert as_tracer(tr) is tr
    assert bool(tr) and tr.enabled


def test_null_span_swallows_nothing():
    """The null span must not suppress exceptions."""
    with pytest.raises(RuntimeError):
        with NULL.span("boom"):
            raise RuntimeError


# ------------------------------------------------------ tracer recording

def test_tracer_records_spans_instants_counters():
    tr = Tracer()
    with tr.span("work", track="main", cat="compute", args={"i": 0}):
        pass
    tr.add_span("transfer", 1.0, 0.25, track="worker 0", cat="transfer")
    tr.instant("gate", 1.25, track="worker 0", args={"upload": True})
    tr.counter("pool_bytes", 2.0, 123.0)
    assert len(tr) == 4
    assert tr.tracks == ["main", "worker 0", "counters"]  # insertion order
    phs = [e[0] for e in tr.events]
    assert phs == ["X", "X", "i", "C"]
    (ph, name, track, cat, t0, dur, args) = tr.events[1]
    assert (name, track, cat, t0, dur) == ("transfer", "worker 0",
                                           "transfer", 1.0, 0.25)
    spans = tr.spans("worker 0")
    assert [s[1] for s in spans] == ["transfer"]


def test_tracer_aggregate_per_track():
    """aggregate() is the one home for phase timing — count/total/max per
    span name, restricted to a track (what the bench reads)."""
    tr = Tracer()
    for dur in (0.1, 0.3, 0.2):
        tr.add_span("step", 0.0, dur, track="pipeline")
    tr.add_span("step", 0.0, 9.0, track="other")
    agg = tr.aggregate("pipeline")
    assert agg["step"]["count"] == 3
    np.testing.assert_allclose(agg["step"]["total_s"], 0.6)
    np.testing.assert_allclose(agg["step"]["max_s"], 0.3)
    assert tr.aggregate()["step"]["count"] == 4


# -------------------------------------------------- chrome-trace export

def test_chrome_trace_export_shape():
    tr = Tracer()
    tr.add_span("compute", 0.5, 1.5, track="worker 0", cat="compute",
                args={"round": 0})
    tr.instant("gate", 2.0, track="worker 0")
    tr.counter("depth", 2.5, 4.0)
    obj = to_chrome_trace(tr, meta={"rule": "cada2"})
    assert obj["otherData"] == {"rule": "cada2"}
    evs = obj["traceEvents"]
    # process name + 2 metadata records per track (name + sort index)
    metas = [e for e in evs if e["ph"] == "M"]
    assert {m["name"] for m in metas} == {"process_name", "thread_name",
                                          "thread_sort_index"}
    x = next(e for e in evs if e["ph"] == "X")
    assert x["ts"] == 0.5e6 and x["dur"] == 1.5e6     # seconds -> µs
    assert x["cat"] == "compute" and x["args"] == {"round": 0}
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["s"] == "t"
    assert validate_chrome_trace(obj) == len(evs)


def test_chrome_trace_validator_rejects_garbage():
    with pytest.raises(ValueError):
        validate_chrome_trace({"notTraceEvents": []})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "X"}]})  # no name/ts
    with pytest.raises(ValueError):
        validate_chrome_trace(
            {"traceEvents": [{"ph": "?", "name": "a", "ts": 0.0,
                              "pid": 1, "tid": 1}]})


def test_export_cli_roundtrip(tmp_path):
    from repro.obs.export import main
    tr = Tracer()
    tr.add_span("round", 0.0, 1.0, track="server")
    path = tmp_path / "trace.json"
    write_chrome_trace(tr, str(path), meta={"runtime": "sim"})
    assert main(["--validate", str(path)]) == 0
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [{"ph": "X"}]}))
    assert main(["--validate", str(bad)]) != 0


# -------------------------------------------------------- registry sinks

def test_metrics_registry_sinks(tmp_path):
    reg = MetricsRegistry()
    reg.counter("uploads").inc(3)
    reg.gauge("pool.resident-bytes").set(512)
    reg.histogram("staleness", bounds=(1, 2, 4)).observe([0, 1, 3, 9])
    with pytest.raises(TypeError):
        reg.gauge("uploads")              # kind mismatch
    jl = tmp_path / "metrics.jsonl"
    reg.write_jsonl(str(jl), extra={"step": 7})
    reg.write_jsonl(str(jl), extra={"step": 8})
    rows = [json.loads(l) for l in jl.read_text().splitlines()]
    assert [r["step"] for r in rows] == [7, 8]
    assert rows[0]["uploads"] == 3.0
    assert rows[0]["staleness"]["count"] == 4
    prom = tmp_path / "metrics.prom"
    reg.write_prom(str(prom))
    text = prom.read_text()
    assert "repro_uploads 3" in text
    assert "repro_pool_resident_bytes 512" in text
    assert 'repro_staleness_bucket{le="+Inf"} 4' in text
    assert "repro_staleness_count 4" in text


# --------------------------------------------------------- ledger parity

@pytest.mark.parametrize("kind", ARMS)
def test_ledger_parity_all_rules(kind):
    """Acceptance gate: for every grad rule and both delta rules, the
    ledger's uploads/bytes totals are BIT-EQUAL to summing the engine's
    own round metrics (which are property-pinned ``bytes_per_upload``
    numbers) — the ledger introduces no second accounting."""
    cohorts = sample_cohorts(M, C, STEPS, seed=3)
    st, pool, mets, eng = _cohort_run(kind, cohorts, pipeline=True)

    led = CommLedger.for_strategy(eng.strategy)
    for met in mets:
        led.observe_round(jax.device_get(met))

    exp_uploads, exp_bytes = 0, 0.0
    for met in mets:
        exp_uploads += int(np.asarray(met["uploads"]))
        exp_bytes += float(np.asarray(met["bytes_up"]))
    assert led.rounds == STEPS
    assert led.uploads == exp_uploads
    assert led.bytes_up == exp_bytes      # bit-equal: same values, same order
    # and the metrics themselves are uploads × the pinned per-upload bytes
    # (priced on the UNPADDED flat length — padding never hits the wire)
    spb = eng.strategy.bytes_per_upload(eng._layout.n)
    assert led.bytes_up == exp_uploads * spb
    # wire-format split: everything lands in this rule's bucket
    s = led.summary()
    wf = eng.strategy.wire_format
    assert s["wire_format"] == wf
    np.testing.assert_allclose(s[f"mbytes_up_{wf}"], led.bytes_up / 1e6)
    for other in {"dense", "quantized", "sparse"} - {wf}:
        assert s[f"mbytes_up_{other}"] == 0.0
    assert sum(s["staleness_hist"].values()) == STEPS * C


def test_wire_format_property():
    as_strat = strategy_for(CommRule(kind="always", c=0.6, d_max=4,
                                     max_delay=10))
    assert as_strat.wire_format == "dense"
    laq = strategy_for(CommRule(kind="laq", c=0.6, d_max=4, max_delay=10))
    assert laq.wire_format == "quantized"
    topk = strategy_for(CommRule(kind="topk", c=0.6, d_max=4, max_delay=10,
                                 topk_frac=0.5, sparse_wire=True))
    assert topk.wire_format == "sparse"


def test_ledger_margin_and_staleness():
    led = CommLedger(rule="cada2")
    led.observe_margin([1.0, -2.0, np.inf, np.nan], 0.5)
    q = led.margin_quantiles()
    assert q["q50"] == pytest.approx((0.5 + (-2.5)) / 2)   # finite only
    led.observe_staleness([0, 0, 3])
    assert led.staleness_hist == {0: 2, 3: 1}
    led.observe_ring(np.array([0, 1, 1, 2]), capacity=5)
    assert led.ring_occupancy == 3 and led.ring_capacity == 5
    led.observe_pending(2)
    led.observe_pending(1)
    assert led.async_pending_max == 2
    with pytest.raises(ValueError):
        CommLedger(wire_format="carrier-pigeon")


# ------------------------------------------------------- sim trace plane

def test_sim_barrier_trace_tracks_and_ledger():
    """A traced WAN barrier sim opens as a valid Chrome trace with one
    track per worker + a server track, and ships a ledger whose totals
    match the SimResult's own counters."""
    m = 3
    params, batches = _problem(m=m, steps=6)
    rule = CommRule(kind="cada2", c=0.6, d_max=4, max_delay=10)
    tr = Tracer()
    res = simulate(logreg_loss, rule, params, batches, n_workers=m,
                   network="wan", mode="barrier", lr=0.01, trace=tr)
    assert set(tr.tracks) >= {f"worker {w}" for w in range(m)} | {"server"}
    agg = tr.aggregate("server")
    assert agg["round"]["count"] == 6
    for w in range(m):
        wa = tr.aggregate(f"worker {w}")
        assert wa["compute"]["count"] == 6
        assert wa["download"]["count"] == 6
    obj = to_chrome_trace(tr)
    validate_chrome_trace(obj)
    # sim clock lands on the µs axis: last event within the sim wall
    max_ts = max(e["ts"] + e.get("dur", 0.0)
                 for e in obj["traceEvents"] if e["ph"] != "M")
    assert max_ts <= res.wall_s * 1e6 * (1 + 1e-9)
    assert res.ledger is not None
    assert res.ledger["uploads"] == res.uploads
    assert res.ledger["bytes_up"] == res.bytes_up


def test_sim_async_trace_and_ledger():
    m = 3
    params, batches = _problem(m=m, steps=12)
    rule = CommRule(kind="cada1", c=0.6, d_max=4, max_delay=8)
    tr = Tracer()
    res = simulate(logreg_loss, rule, params, batches, n_workers=m,
                   network="hetero", mode="async", lr=0.01, trace=tr)
    validate_chrome_trace(to_chrome_trace(tr))
    assert {f"worker {w}" for w in range(m)} <= set(tr.tracks)
    assert tr.aggregate("server").get("apply_update", {}).get("count") \
        == res.steps
    led = res.ledger
    assert led is not None
    assert led["uploads"] == res.uploads
    assert led["rounds"] == res.steps
    assert sum(led["staleness_hist"].values()) > 0


def test_untraced_sim_has_no_tracer_cost_path():
    """trace=None rides the NULL tracer — same results, no events."""
    m = 3
    params, batches = _problem(m=m, steps=6)
    rule = CommRule(kind="cada2", c=0.6, d_max=4, max_delay=10)
    r0 = simulate(logreg_loss, rule, params, batches, n_workers=m,
                  network="wan", mode="barrier", lr=0.01)
    tr = Tracer()
    r1 = simulate(logreg_loss, rule, params, batches, n_workers=m,
                  network="wan", mode="barrier", lr=0.01, trace=tr)
    assert r0.wall_s == r1.wall_s
    np.testing.assert_array_equal(r0.upload_masks, r1.upload_masks)
    assert len(tr) > 0


# ------------------------------------------------- cohort pipeline spans

@pytest.mark.parametrize("pipeline", (False, True))
def test_run_cohort_rounds_pipeline_spans(pipeline):
    """Each cohort round contributes one gather/step/scatter span (plus
    patch spans on the pipelined driver) on the "pipeline" track."""
    cohorts = sample_cohorts(M, C, STEPS, seed=4)
    rule = CommRule(kind="cada2", c=5.0, d_max=4, max_delay=6)
    params, batches = _problem(steps=STEPS)
    cohort_batches = [
        jax.tree.map(lambda x, i=i: x[i][cohorts[i]], batches)
        for i in range(STEPS)]
    eng = CADAEngine(logreg_loss, FusedAMSGrad(lr=0.05), rule, M)
    st, pool = eng.init_cohort(params)
    tr = Tracer()
    st, mets = eng.run_cohort(st, pool, cohort_batches, cohorts,
                              pipeline=pipeline, trace=tr)
    agg = tr.aggregate("pipeline")
    for phase in ("gather", "step", "scatter"):
        assert agg[phase]["count"] == STEPS, (phase, agg)
        assert agg[phase]["total_s"] >= 0.0
    if pipeline:
        from repro.core.flat import cohort_overlap_schedule
        n_overlap = int((cohort_overlap_schedule(cohorts) >= 0)
                        .any(axis=1).sum())
        assert agg.get("patch", {}).get("count", 0) == n_overlap
    validate_chrome_trace(to_chrome_trace(tr))


def test_metrics_out_survives_error():
    """Satellite fix: an exception mid-run must not lose the device-side
    metrics window — ``metrics_out`` keeps every completed round (the
    driver drains in a finally), matching the serial oracle's prefix."""
    j = 9
    cohorts = sample_cohorts(M, C, STEPS, seed=5)
    params, batches = _problem(steps=STEPS)
    cohort_batches = [
        jax.tree.map(lambda x, i=i: x[i][cohorts[i]], batches)
        for i in range(STEPS)]
    rule = CommRule(kind="cada2", c=5.0, d_max=4, max_delay=6)

    class Boom(RuntimeError):
        pass

    # serial oracle over the full schedule
    eng_s = CADAEngine(logreg_loss, FusedAMSGrad(lr=0.05), rule, M)
    st_s, pool_s = eng_s.init_cohort(params)
    _, mets_s = eng_s.run_cohort(st_s, pool_s, cohort_batches, cohorts,
                                 pipeline=False)

    for pipeline in (False, True):
        def exploding(i, cohort):
            if i == j:
                raise Boom
            return cohort_batches[i]

        eng = CADAEngine(logreg_loss, FusedAMSGrad(lr=0.05), rule, M)
        st, pool = eng.init_cohort(params)
        out: list = []
        with pytest.raises(Boom):
            eng.run_cohort(st, pool, exploding, cohorts, pipeline=pipeline,
                           metrics_every=4, metrics_out=out)
        # every COMPLETED round made it out of the device-side window
        assert len(out) >= j - 1, (pipeline, len(out))
        for i, met in enumerate(out):
            for key in ("uploads", "bytes_up", "upload_mask"):
                np.testing.assert_array_equal(
                    np.asarray(met[key]), np.asarray(mets_s[i][key]),
                    err_msg=f"pipeline={pipeline}: metrics_out[{i}][{key}]")


# --------------------------------------------- traced M=10⁴ smoke (CI leg)

def test_obs_smoke_traced_m10k_cohort(tmp_path):
    """The CI obs-smoke: a traced M=10⁴ C=64 pipelined cohort run under
    the 6 GiB cap produces a schema-valid Chrome trace with per-round
    pipeline spans, and the ledger agrees with the round metrics."""
    m, c, rounds = 10_000, 64, 4
    rule = CommRule(kind="cada2", c=0.6, d_max=10, max_delay=100)
    ds = ijcnn1_like(n=20_000)
    mtx = pad_to_matrix(uniform_partition(ds.n, m, seed=0))
    sample = make_cohort_sampler(ds.x, ds.y, mtx, 32)
    params = mlp_init(jax.random.PRNGKey(7), 22, 64, 2)
    eng = CADAEngine(mlp_loss, FusedAMSGrad(lr=0.05), rule, m)
    st, pool = eng.init_cohort(params)
    cohorts = sample_cohorts(m, c, rounds, seed=0)

    def batch_fn(i, cohort):
        return sample(jax.random.PRNGKey(400 + i), jnp.asarray(cohort))

    tr = Tracer()
    mets_out: list = []
    st, mets = eng.run_cohort(st, pool, batch_fn, cohorts, pipeline=True,
                              metrics_every=4, trace=tr,
                              metrics_out=mets_out)
    assert mets is mets_out and len(mets) == rounds
    agg = tr.aggregate("pipeline")
    assert agg["step"]["count"] == rounds
    assert agg["gather"]["count"] == rounds

    led = CommLedger.for_strategy(eng.strategy)
    led.observe_pool(pool)
    for met in mets:
        led.observe_round(jax.device_get(met))
    assert led.uploads == int(sum(int(np.asarray(mm["uploads"]))
                                  for mm in mets))
    assert led.rounds == rounds
    assert led.pool_nbytes == pool.nbytes
    s = led.summary()
    assert s["pool_resident_nbytes"] == pool.resident_nbytes
    assert int(np.asarray(mets[0]["uploads"])) == c   # round 0 force-upload

    path = tmp_path / "cohort_trace.json"
    write_chrome_trace(tr, str(path),
                       meta={"runtime": "cohort", "m": m, "c": c})
    from repro.obs.export import main
    assert main(["--validate", str(path)]) == 0


# ------------------------------------------------- the profiler's clock

def test_tracer_span_lands_on_the_profiler_host_plane(tmp_path):
    """An enabled Tracer's wall-clock span is also a profiler annotation:
    under ``jax.profiler`` it appears on a ``/host:`` plane, named
    ``<track>.<name>``, on the device ops' clock."""
    import glob

    from jax.profiler import ProfileData

    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("gather", track="pipeline"):
            jax.block_until_ready(jnp.ones(8) + 1)
    finally:
        jax.profiler.stop_trace()
    assert tr.aggregate("pipeline")["gather"]["count"] == 1
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = [e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events]
    assert names.count("pipeline.gather") == 1


def test_run_mesh_traces_dispatch_and_wait(tmp_path):
    """The launcher's mesh loop times the step's enqueue (``dispatch``,
    every step) apart from the fetch of its scalars (``wait``, on the
    steps it logs: 0 and the last of 3 at ``--log-every 2``)."""
    import repro.configs as RC
    from repro.launch.train import build_parser, rule_from_args, run_mesh

    path = tmp_path / "mesh_trace.json"
    args = build_parser().parse_args([
        "--arch", "stablelm-1.6b", "--smoke", "--rule", "cada2",
        "--steps", "3", "--global-batch", "4", "--seq", "16",
        "--workers", "2", "--log-every", "2", "--trace", str(path)])
    run_mesh(RC.get_smoke_config(args.arch), rule_from_args(args), args)
    spans = [e for e in json.loads(path.read_text())["traceEvents"]
             if e["ph"] == "X"]
    steps = lambda name: [e["args"]["step"] for e in spans  # noqa: E731
                          if e["name"] == name]
    assert steps("dispatch") == [0, 1, 2]
    assert steps("wait") == [0, 2]
    assert not steps("train_step")
