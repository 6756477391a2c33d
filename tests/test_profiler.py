"""Schedule-aware profiler (docs/observability.md "Profiling & Tracing").

Per-leg micro-run timing + trace-span parsing (LegProfiler / LegSample),
leg-granular calibration (fit_leg_constants round-trips on planted
constants and on a recorded sample set), calibration.json
persistence + automatic consumption by estimate_ir_cost and
AutoStrategy(search=True) (the constants provably reach the ranking),
Chrome-trace export validated against the Trace Event Format contract
Perfetto requires, cross-host aggregation exactness + the straggler
verdict, the telemetry/leg-drift and telemetry/straggler lint rules,
serving request-trace propagation (router header -> scheduler spans),
and the CLI --compare / --export-trace surfaces.
"""
import gzip
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.kernel.synchronization import schedule_ir as sir
from autodist_tpu.telemetry import aggregate as agg
from autodist_tpu.telemetry import calibration as cal
from autodist_tpu.telemetry import profiler as prof
from autodist_tpu.telemetry import registry as reg
from autodist_tpu.telemetry import timeline as tl
from autodist_tpu.telemetry import trace_export as tx

pytestmark = pytest.mark.profiler

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "data", "profiler")


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("AUTODIST_TELEMETRY", raising=False)
    monkeypatch.delenv("AUTODIST_TELEMETRY_DIR", raising=False)
    monkeypatch.delenv("AUTODIST_CALIBRATION", raising=False)
    cal.reset_calibration_cache_for_testing()
    prof.reset_spans_for_testing()
    reg.reset_for_testing()
    yield
    cal.reset_calibration_cache_for_testing()
    prof.reset_spans_for_testing()
    reg.reset_for_testing()


def _zero1_ir(n_vars=4, d=8, accum=1, guard=False):
    facts = [sir.PlanFact(name=f"w{i}", shape=(256, 256), dtype="float32",
                          sync_kind="AllReduce",
                          sync_mode="reduce_scatter",
                          bucket_bytes=1 << 18, overlap="auto")
             for i in range(n_vars)]
    return sir.ir_from_facts(facts, axes={"data": d}, accum_steps=accum,
                             guard=guard)


# -- LegSample + persistence -------------------------------------------------

def test_leg_sample_roundtrip(tmp_path):
    s = prof.LegSample(schedule_fingerprint="abc", leg_id="b@-1/reduce",
                       kind="reduce_scatter", measured_s=1.5e-4,
                       alg="ring", nbytes=1 << 20, slot=-1,
                       predicted_s=2e-4, host="h1", time_unix=12.0)
    back = prof.LegSample.from_dict(json.loads(s.to_json()))
    assert back == s
    # unknown keys are dropped, not fatal (forward compatibility)
    d = json.loads(s.to_json())
    d["future_field"] = 1
    assert prof.LegSample.from_dict(d).leg_id == s.leg_id

    path = prof.write_leg_samples([s, s], str(tmp_path))
    assert path and os.path.exists(path)
    loaded = prof.load_leg_samples(str(tmp_path))
    assert len(loaded) == 2 and loaded[0].kind == "reduce_scatter"


def test_profile_ir_microbench_covers_every_leg():
    """Micro-runs produce one sample per leg, with positive measured
    times, stamped fingerprints, and leg-priced predictions."""
    ir = _zero1_ir(guard=True)
    samples = prof.LegProfiler(warmup=1, repeats=2).profile_ir(ir)
    assert len(samples) == len(ir.legs)
    by_id = {s.leg_id for s in samples}
    assert by_id == {l.id for l in ir.legs}
    for s in samples:
        assert s.measured_s > 0
        assert s.schedule_fingerprint == ir.fingerprint()
        assert s.kind in cal.LEG_KINDS
    # collective legs carry a prediction from the leg-priced model
    coll = [s for s in samples if s.kind != "update"]
    assert coll and all(s.predicted_s is not None and s.predicted_s > 0
                        for s in coll)
    # the per-kind exposed-ms gauge landed on the process registry
    names = {(m.name, tuple(sorted(m.labels.items())))
             for m in reg.DEFAULT_REGISTRY.metrics()}
    assert any(n == "autodist_leg_exposed_ms" for n, _ in names)


def test_span_kind_mapping():
    assert prof.span_leg_kind(
        "autodist_sync/ring_reduce_scatter/leg2") == "ppermute_hop"
    assert prof.span_leg_kind(
        "autodist_sync/param_gather/bucketA") == "all_gather"
    assert prof.span_leg_kind("autodist_sync/guard_rollup") == "psum_guard"
    assert prof.span_leg_kind(
        "autodist_sync/zero1_shard_update") == "update"
    assert prof.span_leg_kind(
        "autodist_sync/bucket_reduce/b0") == "all_reduce"
    assert prof.span_leg_kind(
        "jit(step)/autodist_sync/quant_ring_all_gather/leg1") \
        == "ppermute_hop"
    assert prof.span_leg_kind("some_matmul_fusion") is None


def test_parse_profiler_trace(tmp_path):
    """A jax-profiler-shaped trace file (gzipped Chrome JSON) yields
    trace-sourced samples for exactly the autodist_sync spans."""
    events = [
        {"name": "autodist_sync/bucket_reduce/b0", "ph": "X",
         "ts": 10.0, "dur": 250.0, "pid": 1, "tid": 1},
        {"name": "autodist_sync/ring_all_gather/leg1", "ph": "X",
         "ts": 300.0, "dur": 80.0, "pid": 1, "tid": 1},
        {"name": "fusion.42", "ph": "X", "ts": 0.0, "dur": 1000.0,
         "pid": 1, "tid": 1},
        {"name": "autodist_sync/guard_rollup", "ph": "X",
         "ts": 400.0, "dur": 5.5, "pid": 1, "tid": 1},
    ]
    sub = tmp_path / "plugins" / "profile" / "run1"
    sub.mkdir(parents=True)
    with gzip.open(sub / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    samples = prof.LegProfiler().parse_trace(str(tmp_path),
                                             schedule_fingerprint="fp9")
    kinds = sorted(s.kind for s in samples)
    assert kinds == ["all_reduce", "ppermute_hop", "psum_guard"]
    assert all(s.source == "trace" for s in samples)
    assert samples[0].schedule_fingerprint == "fp9"
    by_kind = {s.kind: s.measured_s for s in samples}
    assert by_kind["all_reduce"] == pytest.approx(250e-6)
    assert by_kind["psum_guard"] == pytest.approx(5.5e-6)


# -- leg calibration ---------------------------------------------------------

def test_fit_leg_constants_planted_roundtrip():
    """Samples generated from known per-kind constants recover those
    constants (distinct ring-hop vs one-shot alphas included)."""
    true = {"all_reduce": (2e-5, 1e10), "ppermute_hop": (5e-6, 2e10),
            "all_gather": (1e-5, 4e10), "update": (0.0, 8e11)}
    samples = []
    for kind, (a, bw) in true.items():
        for nb in (1 << 16, 1 << 18, 1 << 20, 1 << 22):
            samples.append(prof.LegSample(
                schedule_fingerprint="fp", leg_id=f"{kind}/{nb}",
                kind=kind, measured_s=a + nb / bw, nbytes=nb))
    fitted = cal.fit_leg_constants(samples)
    assert fitted is not None and fitted.n_samples == len(samples)
    for kind, (a, bw) in true.items():
        assert fitted.alphas[kind] == pytest.approx(a, abs=1e-9)
        assert fitted.bandwidths[kind] == pytest.approx(bw, rel=1e-6)
    # the ring-hop launch cost fit independently of the one-shot one
    assert fitted.alphas["ppermute_hop"] != fitted.alphas["all_reduce"]
    # round trip through the JSON schema
    back = cal.LegCalibration.from_dict(fitted.to_dict())
    assert back.bandwidths == fitted.bandwidths
    assert back.alphas == fitted.alphas


def test_fit_leg_constants_quant_overhead():
    """Quantized samples' residual over the full-precision model fits
    the quantize/dequantize per-byte overhead."""
    samples = []
    a, bw, q = 1e-5, 1e10, 3e-12
    for nb in (1 << 18, 1 << 20, 1 << 22):
        samples.append(prof.LegSample(
            schedule_fingerprint="fp", leg_id=f"f32/{nb}",
            kind="all_reduce", measured_s=a + nb / bw, nbytes=nb))
        samples.append(prof.LegSample(
            schedule_fingerprint="fp", leg_id=f"int8/{nb}",
            kind="all_reduce", measured_s=a + nb / bw + q * nb,
            nbytes=nb, compressor="Int8Compressor"))
    fitted = cal.fit_leg_constants(samples)
    assert fitted.quant_overhead_per_byte == pytest.approx(q, rel=1e-3)
    assert fitted.leg_time_s("all_reduce", 1 << 20, quantized=True) > \
        fitted.leg_time_s("all_reduce", 1 << 20)


def test_fit_leg_constants_record_scale_and_acceptance():
    """With StepRecords, the fit learns a step-level scale and scores
    leg-calibrated MAE against the whole-step fit — the acceptance
    comparison (median-anchored leg fit <= mean-anchored step fit on a
    skewed record set)."""
    samples = [prof.LegSample(
        schedule_fingerprint="fpA", leg_id=f"l{i}", kind="all_reduce",
        measured_s=1e-4, nbytes=1 << 20, slot=-1) for i in range(4)]
    rng = np.random.RandomState(0)
    records = [tl.StepRecord(
        step=i, time_unix=float(i), schedule_fingerprint="fpA",
        step_time_s=8e-4 + abs(float(rng.randn())) * 2e-4,
        exposed_bytes=4 * (1 << 20), num_collectives=4)
        for i in range(64)]
    fitted = cal.fit_leg_constants(samples, records)
    assert fitted.n_records == 64
    assert fitted.scale > 0
    pred = fitted.predict_step_time_s("fpA")
    assert pred == pytest.approx(fitted.scale * 4e-4)
    assert fitted.mean_abs_error_s is not None
    assert fitted.step_fit_mean_abs_error_s is not None
    assert fitted.improved, (
        f"leg-calibrated MAE {fitted.mean_abs_error_s} must be <= "
        f"whole-step fit MAE {fitted.step_fit_mean_abs_error_s}")
    # the whole-step pair rode along for estimate_cost consumers
    assert fitted.ici_bandwidth > 0 and fitted.alpha >= 0


def _read_jsonl(name, from_dict):
    with open(os.path.join(DATA, name), encoding="utf-8") as f:
        return [from_dict(json.loads(line)) for line in f if line.strip()]


def test_recorded_samples_roundtrip_through_fit_leg_constants():
    """A recorded sample set round-trips through the fit: leg samples
    of four schedules (every leg kind a micro-run produced) plus the
    step records of the one fingerprint that has them (a cut of one
    8-device CPU-mesh run, kept under ``tests/data/profiler/``; the
    seconds in it are inputs to the arithmetic, not measurements of
    anything) produce a calibration whose record error meets the
    acceptance bar (leg-calibrated MAE <= the whole-step fit's)."""
    samples = _read_jsonl("leg_samples.jsonl", prof.LegSample.from_dict)
    records = _read_jsonl("step_records.jsonl", tl.StepRecord.from_dict)
    assert samples and records
    fitted = cal.fit_leg_constants(samples, records)
    assert fitted is not None
    assert set(fitted.bandwidths) == {s.kind for s in samples}
    assert set(fitted.fingerprints) == {s.schedule_fingerprint
                                        for s in samples}
    step_fit = cal.fit_constants(records)
    assert step_fit is not None
    assert fitted.n_records > 0 and fitted.mean_abs_error_s is not None
    assert fitted.mean_abs_error_s <= step_fit.mean_abs_error_s + 1e-9


def test_calibration_json_roundtrip_and_discovery(tmp_path, monkeypatch):
    fitted = cal.LegCalibration(
        alphas={"all_reduce": 1e-5}, bandwidths={"all_reduce": 1e10},
        ici_bandwidth=2e10, alpha=3e-6, n_samples=7)
    path = cal.save_calibration(fitted, str(tmp_path / "calibration.json"))
    assert cal.load_calibration(path).bandwidths == fitted.bandwidths
    # no env -> no automatic discovery (estimates stay reproducible)
    assert cal.load_default_calibration() is None
    monkeypatch.setenv("AUTODIST_CALIBRATION", path)
    cal.reset_calibration_cache_for_testing()
    got = cal.load_default_calibration()
    assert got is not None and got.ici_bandwidth == 2e10
    # TELEMETRY_DIR discovery path
    monkeypatch.delenv("AUTODIST_CALIBRATION")
    monkeypatch.setenv("AUTODIST_TELEMETRY_DIR", str(tmp_path))
    cal.reset_calibration_cache_for_testing()
    assert cal.load_default_calibration().ici_bandwidth == 2e10
    # corrupt file degrades to None, never raises
    with open(path, "w") as f:
        f.write("{not json")
    cal.reset_calibration_cache_for_testing()
    assert cal.load_default_calibration() is None


def test_estimate_ir_cost_consumes_leg_constants(monkeypatch, tmp_path):
    """The leg-calibrated path changes the estimate (per-kind pricing +
    the update term), and the environment-discovered calibration.json
    is picked up with NO flags."""
    from autodist_tpu.strategy.cost_model import estimate_ir_cost

    ir = _zero1_ir()
    base = estimate_ir_cost(ir)
    slow = cal.LegCalibration(
        alphas={k: 1e-3 for k in cal.LEG_KINDS},
        bandwidths={k: 1e6 for k in cal.LEG_KINDS})
    fast = cal.LegCalibration(
        alphas={k: 0.0 for k in cal.LEG_KINDS},
        bandwidths={k: 1e15 for k in cal.LEG_KINDS})
    t_slow = estimate_ir_cost(ir, constants=slow).time_s
    t_fast = estimate_ir_cost(ir, constants=fast).time_s
    assert t_slow > base.time_s > t_fast
    # byte accounting is calibration-independent
    assert estimate_ir_cost(ir, constants=slow).wire_bytes == \
        base.wire_bytes
    # automatic discovery: same result as passing constants explicitly
    path = cal.save_calibration(slow, str(tmp_path / "calibration.json"))
    monkeypatch.setenv("AUTODIST_CALIBRATION", path)
    cal.reset_calibration_cache_for_testing()
    assert estimate_ir_cost(ir).time_s == pytest.approx(t_slow)


def test_auto_strategy_consumes_calibration(monkeypatch, tmp_path):
    """AutoStrategy(search=True) ranks with calibration.json constants
    without flags: launch-dominated constants flip the big-dense pick
    from Zero1 (the wire/update-dominated default) to AllReduce (one
    collective launch) — proof the constants reach the ranking."""
    from autodist_tpu.graph_item import GraphItem
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy import AutoStrategy

    spec = ResourceSpec(resource_info={
        "nodes": [{"address": "localhost", "chips": 8, "chief": True}]})
    gi = GraphItem({"w": jnp.zeros((2048, 2048), jnp.float32)})
    baseline = AutoStrategy(search=True)
    baseline.build(gi, spec)
    assert baseline.last_choice == "Zero1"

    path = cal.save_calibration(
        cal.LegCalibration(ici_bandwidth=1e15, alpha=1.0),
        str(tmp_path / "calibration.json"))
    monkeypatch.setenv("AUTODIST_CALIBRATION", path)
    cal.reset_calibration_cache_for_testing()
    calibrated = AutoStrategy(search=True)
    calibrated.build(gi, spec)
    assert calibrated.last_choice == "AllReduce"

    # sane measured constants CONFIRM the default pick (calibration
    # changes the ranking only when measurement disagrees)
    path2 = cal.save_calibration(
        cal.LegCalibration(ici_bandwidth=4.5e10, alpha=5e-6),
        str(tmp_path / "calibration2.json"))
    monkeypatch.setenv("AUTODIST_CALIBRATION", path2)
    cal.reset_calibration_cache_for_testing()
    confirmed = AutoStrategy(search=True)
    confirmed.build(gi, spec)
    assert confirmed.last_choice == "Zero1"


# -- trace export ------------------------------------------------------------

def _assert_valid_chrome_trace(payload):
    """The Trace Event Format contract Perfetto's importer enforces:
    a traceEvents array of objects, each with a string name, a known
    phase, numeric non-negative ts (except metadata), and a numeric
    dur on complete events; pids/tids integral."""
    assert isinstance(payload, dict)
    events = payload["traceEvents"]
    assert isinstance(events, list) and events
    for ev in events:
        assert isinstance(ev["name"], str) and ev["name"]
        assert ev["ph"] in ("X", "i", "M", "B", "E", "C")
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int)
        if ev["ph"] == "M":
            continue
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        if ev["ph"] == "i":
            assert ev.get("s") in ("t", "p", "g")
    return events


def _make_run_dir(tmp_path, hosts=("hostA", "hostB")):
    """A run directory holding all four streams across two hosts."""
    run = tmp_path / "run"
    run.mkdir()
    t0 = 1000.0
    for hi, host in enumerate(hosts):
        with open(run / f"steps-{host}-{100 + hi}.jsonl", "w") as f:
            for i in range(6):
                r = tl.StepRecord(
                    step=i, time_unix=t0 + i * 0.01 + 0.01,
                    step_time_s=0.01 * (1 + hi), host=host,
                    phases={"data_load": 0.001, "dispatch": 0.002},
                    loss=1.0 / (i + 1), schedule_fingerprint="fpX")
                f.write(r.to_json() + "\n")
        with open(run / f"events-{host}-{100 + hi}.jsonl", "w") as f:
            f.write(json.dumps({"time": t0 + 0.02, "kind": "chaos/kill",
                                "host": host, "pid": 100 + hi,
                                "step": 2}) + "\n")
    prof.write_leg_samples(
        [prof.LegSample(schedule_fingerprint="fpX", leg_id="b0@-1/reduce",
                        kind="reduce_scatter", measured_s=2e-4,
                        nbytes=1 << 20, predicted_s=1e-4, host=hosts[0],
                        time_unix=t0 + 0.005)], str(run))
    w = prof._SpanWriter(directory=str(run))
    w.record("queue_wait", start_unix=t0 + 0.03, dur_s=0.002,
             trace_id="t123", request_id=7, slo="latency")
    w.record("request", start_unix=t0 + 0.03, dur_s=0.05,
             trace_id="t123", request_id=7)
    w.close()
    return run


def test_export_trace_golden(tmp_path):
    """One merged trace file from a run directory holding StepRecords,
    journal events, leg samples, and serving spans — valid Chrome
    trace, per-host process tracks, every stream represented, trace id
    preserved."""
    run = _make_run_dir(tmp_path)
    path = tx.export_trace(str(run))
    assert path == str(run / "trace.json")
    with open(path, encoding="utf-8") as f:
        payload = json.load(f)
    events = _assert_valid_chrome_trace(payload)
    # per-host process tracks
    names = [e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "process_name"]
    assert "hostA" in names and "hostB" in names
    cats = {e.get("cat") for e in events if e["ph"] != "M"}
    assert {"train", "phase", "leg", "event", "serving"} <= cats
    # steps from both hosts landed with their phases nested inside
    steps = [e for e in events if e.get("cat") == "train"]
    assert len(steps) == 12          # 2 hosts x 6 steps, all timed
    # the serving spans carry the propagated trace id
    serving = [e for e in events if e.get("cat") == "serving"]
    assert serving and all(
        e["args"]["trace_id"] == "t123" for e in serving)
    # stream counts in the exporter's own provenance
    streams = payload["otherData"]["streams"]
    assert streams["serving_spans"] == 2
    assert streams["leg_samples"] == 1
    assert streams["journal_events"] == 2
    # empty directory -> nothing to export
    empty = tmp_path / "empty"
    empty.mkdir()
    assert tx.export_trace(str(empty)) is None


# -- cross-host aggregation --------------------------------------------------

def test_registry_snapshot_merge_exact(tmp_path):
    """Two hosts' registry snapshots merge into exactly what one global
    registry would hold (fixed-bound histograms + counters)."""
    bounds = (0.01, 0.1, 1.0)
    rng = np.random.RandomState(3)
    a, b = reg.MetricsRegistry(), reg.MetricsRegistry()
    oracle = reg.Histogram("lat_seconds", buckets=bounds)
    for r_, n in ((a, 50), (b, 77)):
        h = r_.histogram("lat_seconds", buckets=bounds)
        for v in rng.uniform(0, 2, n):
            h.observe(v)
            oracle.observe(v)
        r_.counter("steps_total").inc(n)
    agg.write_registry_snapshot(str(tmp_path), a)
    # distinct filename per writer: fake a second host's snapshot
    with open(tmp_path / "metrics-hostB-42.json", "w") as f:
        json.dump(b.to_dict(), f)
    merged = agg.merge_registry_snapshots(str(tmp_path))
    h = merged.histogram("lat_seconds", buckets=bounds)
    assert h.counts == oracle.counts and h.count == oracle.count
    assert merged.counter("steps_total").value == 127


def test_per_host_stats_and_straggler(tmp_path):
    run = _make_run_dir(tmp_path)          # hostB is 2x hostA
    records = tl.load_step_records(str(run))
    hosts = agg.per_host_step_stats(records)
    assert set(hosts) == {"hostA", "hostB"}
    assert hosts["hostA"]["median_s"] == pytest.approx(0.01)
    assert hosts["hostB"]["median_s"] == pytest.approx(0.02)
    out = agg.aggregate_run(str(run))
    assert out["step_skew_ratio"] == pytest.approx(2.0)
    assert out["straggler"] and "hostB" in out["straggler"]
    assert out["straggler_count"] == 1
    # the fleet gauges landed on the process registry
    vals = {m.name: m.value for m in reg.DEFAULT_REGISTRY.metrics()}
    assert vals["autodist_host_step_skew_ratio"] == pytest.approx(2.0)
    assert vals["autodist_straggler_count"] == 1
    # single-host runs are never stragglers
    assert cal.straggler_reason({"only": 0.5}) is None
    assert cal.straggler_reason(
        {"a": 0.010, "b": 0.014}) is None       # under 1.5x


# -- analysis rules ----------------------------------------------------------

def test_leg_drift_and_straggler_lint():
    """The telemetry pass surfaces the new rules from provenance via
    the shared pure rule strings."""
    from tests._analysis_fixtures import AXES8, full_cover, make_gi

    from autodist_tpu.analysis import analyze

    gi = make_gi()
    strat = full_cover(gi)
    tel = {
        "measured_step_time_s": 0.010, "predicted_step_time_s": 0.009,
        "leg_kinds": {
            "reduce_scatter": {"measured_s": 9e-4, "predicted_s": 1e-4},
            "all_gather": {"measured_s": 1.1e-4, "predicted_s": 1e-4},
        },
        "per_host_step_time_s": {"h0": 0.010, "h1": 0.021},
    }
    report = analyze(strat, gi, mesh=AXES8, telemetry=tel,
                     passes=("telemetry",))
    rules = [d.rule for d in report.diagnostics]
    assert "telemetry/leg-drift" in rules
    assert "telemetry/straggler" in rules
    assert "telemetry/model-drift" not in rules     # step ratio is fine
    drift = next(d for d in report.diagnostics
                 if d.rule == "telemetry/leg-drift")
    assert drift.message == cal.leg_drift_reason(
        "reduce_scatter", 9e-4, 1e-4)
    assert drift.location == "reduce_scatter"       # WHICH kind drifted
    straggler = next(d for d in report.diagnostics
                     if d.rule == "telemetry/straggler")
    assert straggler.message == cal.straggler_reason(
        {"h0": 0.010, "h1": 0.021})
    # aggregate_run output accepted directly (hosts mapping)
    report2 = analyze(strat, gi, mesh=AXES8, passes=("telemetry",),
                      telemetry={"hosts": {
                          "h0": {"median_s": 0.010},
                          "h1": {"median_s": 0.030}}})
    assert any(d.rule == "telemetry/straggler"
               for d in report2.diagnostics)


# -- serving request tracing -------------------------------------------------

@pytest.fixture(scope="module")
def lm():
    from autodist_tpu.models.transformer import dense_attention
    from autodist_tpu.models.transformer_lm import transformer_lm

    spec = transformer_lm(vocab_size=61, num_layers=2, num_heads=2,
                          head_dim=8, d_ff=32, max_len=48, seq_len=16,
                          attn_fn=dense_attention)
    params = spec.init(jax.random.PRNGKey(0))
    return spec, params


def test_scheduler_emits_request_spans(lm, tmp_path):
    """A paged request submitted with a trace id lands queue-wait /
    prefill / decode spans tagged with that id in the span stream, and
    pop_timings carries the id for the HTTP layer."""
    from autodist_tpu.serving import PagedDecodeEngine

    prof.configure_spans(str(tmp_path))
    spec, params = lm
    eng = PagedDecodeEngine(spec, params, slots=2, window=32,
                            block_size=8, num_blocks=24, chunk=4)
    rng = np.random.RandomState(0)
    rid = eng.submit(rng.randint(0, 61, 4).astype(np.int32), 5,
                     trace_id="trace-xyz")
    results = eng.run()
    assert rid in results
    timings = eng.pop_timings()
    assert timings[rid]["trace_id"] == "trace-xyz"
    # the engine's own tick spans (engine/...) share the stream; the
    # request's lifecycle is the records that carry its id
    spans = [s for s in prof.load_spans(str(tmp_path))
             if s["ids"].get("request_id") == rid]
    by_name = {s["name"]: s for s in spans}
    assert {"queue_wait", "prefill", "decode"} <= set(by_name)
    for s in spans:
        assert s["trace_id"] == "trace-xyz"
        assert s["dur_s"] >= 0 and s["start_unix"] > 0
        assert s["end"] - s["start"] == pytest.approx(s["dur_s"])
    assert by_name["decode"]["ids"]["generated"] == 5
    # spans order: queue_wait starts <= prefill starts <= decode starts
    assert by_name["queue_wait"]["start_unix"] <= \
        by_name["prefill"]["start_unix"] <= \
        by_name["decode"]["start_unix"]
    eng.assert_no_leaks()


def test_router_trace_id_propagation_and_fallback():
    """The router passes one trace id per logical request to endpoints
    that accept it, and degrades cleanly for duck-typed endpoints that
    predate trace propagation."""
    from autodist_tpu.serving.router import Router

    seen = {}

    class Traced:
        name = "traced"

        def probe(self, timeout=2.0):
            return True

        def fetch_stats(self):
            return {"outstanding": 0}

        def post(self, body, timeout, trace_id=""):
            seen["trace_id"] = trace_id
            return 200, {"ok": True}

    class Legacy:
        name = "legacy"

        def probe(self, timeout=2.0):
            return True

        def fetch_stats(self):
            return {"outstanding": 0}

        def post(self, body, timeout):
            seen["legacy"] = True
            return 200, {"ok": True}

    r = Router([Traced()])
    assert r.complete({"prompt_tokens": [1]})["ok"]
    assert seen["trace_id"]                     # non-empty id propagated
    r2 = Router([Legacy()])
    assert r2.complete({"prompt_tokens": [1]})["ok"]
    assert seen.get("legacy")                   # old signature still works


# -- CLI ---------------------------------------------------------------------

def test_cli_export_trace_and_compare(tmp_path, capsys):
    from autodist_tpu.telemetry.__main__ import main

    run_a = _make_run_dir(tmp_path)
    # run B: same shape, hostA 30% slower -> a step-time regression
    run_b = tmp_path / "run_b"
    run_b.mkdir()
    with open(run_b / "steps-hostA-100.jsonl", "w") as f:
        for i in range(6):
            r = tl.StepRecord(step=i, time_unix=2000.0 + i * 0.02,
                              step_time_s=0.013, host="hostA",
                              phases={"data_load": 0.004})
            f.write(r.to_json() + "\n")
    prof.write_leg_samples(
        [prof.LegSample(schedule_fingerprint="fpX", leg_id="b0@-1/reduce",
                        kind="reduce_scatter", measured_s=9e-4,
                        nbytes=1 << 20, predicted_s=1e-4,
                        time_unix=2000.0)], str(run_b))

    assert main([str(run_a), "--export-trace"]) == 0
    out = capsys.readouterr().out
    assert "trace.json" in out
    with open(run_a / "trace.json", encoding="utf-8") as f:
        _assert_valid_chrome_trace(json.load(f))

    assert main([str(run_a), "--compare", str(run_b), "--json"]) == 0
    cmp = json.loads(capsys.readouterr().out)
    # hostA went 10ms -> 13ms, but run_a's p50 includes hostB's 20ms
    assert cmp["step_time"]["p50_ms"]["a"] is not None
    assert cmp["leg_kinds"]["reduce_scatter"]["delta_pct"] > 3
    assert "drift" in cmp["leg_kinds"]["reduce_scatter"]
    assert any("reduce_scatter" in r for r in cmp["regressions"])
    # human form renders without blowing up
    assert main([str(run_a), "--compare", str(run_b)]) == 0
    human = capsys.readouterr().out
    assert "REGRESSIONS" in human
    # summary path picks up hosts + leg kinds + straggler
    assert main([str(run_a)]) == 0
    summary = capsys.readouterr().out
    assert "telemetry/straggler" in summary
    assert "leg reduce_scatter" in summary


def test_cli_fit_saves_calibration(tmp_path, capsys):
    from autodist_tpu.telemetry.__main__ import main

    run = _make_run_dir(tmp_path)
    assert main([str(run), "--fit", "--save-calibration", "-",
                 "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["leg_calibration"]["n_samples"] == 1
    saved = cal.load_calibration(str(run / "calibration.json"))
    assert saved is not None and "reduce_scatter" in saved.bandwidths


def test_profile_ir_on_real_session_mesh():
    """End to end on a live session: the session's verified IR
    micro-profiles on its own mesh, samples join records through
    fit_leg_constants, and the calibrated estimate_ir_cost prices the
    same IR."""
    import optax

    from autodist_tpu.autodist import AutoDist, \
        _reset_default_autodist_for_testing
    from autodist_tpu.strategy import Zero1
    from autodist_tpu.strategy.cost_model import estimate_ir_cost

    _reset_default_autodist_for_testing()
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(64, 64) * 0.05, jnp.float32)}
    batch = {"x": rng.randn(8, 64).astype(np.float32)}

    def loss_fn(p, b):
        return jnp.mean((b["x"] @ p["w"]) ** 2)

    ad = AutoDist(strategy_builder=Zero1(bucket_bytes=1 << 16))
    with ad.scope():
        ad.capture(params=params, optimizer=optax.sgd(1e-3),
                   loss_fn=loss_fn)
    sess = ad.create_distributed_session()
    ir = sess.schedule_ir
    assert ir is not None
    samples = prof.LegProfiler(mesh=sess.mesh, warmup=1,
                               repeats=2).profile_ir(ir)
    assert len(samples) == len(ir.legs)
    for _ in range(4):
        sess.run(batch)
    records = sess.telemetry.records if sess.telemetry else []
    fitted = cal.fit_leg_constants(samples, records)
    assert fitted is not None
    report = estimate_ir_cost(ir, constants=fitted)
    assert report.time_s > 0
    _reset_default_autodist_for_testing()
