"""Unified telemetry subsystem (docs/observability.md).

Registry merge exactness, disabled-path no-ops, StepRecord
flush/rotation, the event journal round-trip, Prometheus rendering, the
serving /metrics endpoint, calibration fit (planted constants + real
recorded runs), the ``telemetry/model-drift`` lint, the session/fit
integration (phase timers, health annotations, heartbeat snapshots),
re-armable trace windows (AUTODIST_TRACE_AT), and the
``python -m autodist_tpu.telemetry`` CLI.
"""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.telemetry import calibration as cal
from autodist_tpu.telemetry import events as ev
from autodist_tpu.telemetry import registry as reg
from autodist_tpu.telemetry import timeline as tl

pytestmark = pytest.mark.telemetry


@pytest.fixture(autouse=True)
def _clean_telemetry(monkeypatch):
    monkeypatch.delenv("AUTODIST_TELEMETRY", raising=False)
    monkeypatch.delenv("AUTODIST_TELEMETRY_DIR", raising=False)
    ev.reset_for_testing()
    yield
    ev.reset_for_testing()


# -- registry ----------------------------------------------------------------

def test_counter_gauge_basics():
    r = reg.MetricsRegistry()
    c = r.counter("reqs_total", "requests")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("depth", "queue depth")
    g.set(7)
    g.dec(2)
    assert g.value == 5
    # get-or-create is idempotent; kind mismatch is loud
    assert r.counter("reqs_total") is c
    with pytest.raises(ValueError):
        r.gauge("reqs_total")


def test_histogram_merge_is_exact_across_hosts():
    """The cross-host merge contract: two per-host histograms with the
    same fixed bounds merge into EXACTLY what one global histogram
    observing the union would hold — counts, sum, and count."""
    bounds = (0.01, 0.1, 1.0)
    rng = np.random.RandomState(0)
    a_samples = list(rng.uniform(0, 2, 100))
    b_samples = list(rng.uniform(0, 2, 137))

    host_a = reg.Histogram("h", buckets=bounds)
    host_b = reg.Histogram("h", buckets=bounds)
    oracle = reg.Histogram("h", buckets=bounds)
    for v in a_samples:
        host_a.observe(v)
        oracle.observe(v)
    for v in b_samples:
        host_b.observe(v)
        oracle.observe(v)

    host_a.merge(host_b)
    assert host_a.counts == oracle.counts
    assert host_a.count == oracle.count
    assert host_a.sum == pytest.approx(oracle.sum)

    # JSON-transport merge (chief side) is the same operation.
    r = reg.MetricsRegistry()
    r.histogram("h", buckets=bounds)
    r.merge_dict([host_b.to_dict()])
    merged = r.histogram("h", buckets=bounds)
    for v in a_samples:
        merged.observe(v)
    assert merged.counts == oracle.counts

    # Mismatched bounds must refuse, not re-bin approximately.
    other = reg.Histogram("h", buckets=(0.5, 5.0))
    with pytest.raises(ValueError, match="bounds differ"):
        host_a.merge(other)


def test_histogram_percentile():
    h = reg.Histogram("h", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.5, 3.0):
        h.observe(v)
    p50 = h.percentile(0.5)
    assert 1.0 <= p50 <= 2.0
    assert h.percentile(1.0) == 4.0


def test_disabled_path_is_noop(monkeypatch):
    monkeypatch.setenv("AUTODIST_TELEMETRY", "0")
    c = reg.counter("x_total")
    assert c is reg.NULL_METRIC
    c.inc()                      # must not throw, must not allocate
    assert reg.histogram("h") is reg.NULL_METRIC
    assert tl.StepRecorder.create("run") is None
    assert ev.emit_event("anything", a=1) is None
    # and nothing landed on the default registry / journal
    assert all(m.name != "x_total"
               for m in reg.DEFAULT_REGISTRY.metrics())


def test_prometheus_rendering():
    r = reg.MetricsRegistry()
    r.counter("steps_total", "steps run").inc(3)
    h = r.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(2.0)
    text = reg.render_prometheus(r)
    assert "# TYPE steps_total counter" in text
    assert "steps_total 3" in text
    assert "# TYPE lat_seconds histogram" in text
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text


# -- step records ------------------------------------------------------------

def test_step_record_flush_and_rotation(tmp_path):
    rec = tl.StepRecorder("r", directory=str(tmp_path), flush_every=2,
                          rotate_records=3)
    for i in range(8):
        rec.add_phase("data_load", 0.002)
        rec.record_step(i, items=4)
    rec.flush()
    files = sorted(p for p in os.listdir(tmp_path)
                   if p.startswith("steps-"))
    assert len(files) == 3           # 8 records at 3/segment
    loaded = tl.load_step_records(str(tmp_path))
    assert [r.step for r in loaded] == list(range(8))
    assert loaded[3].phases["data_load"] == pytest.approx(0.002)
    assert loaded[1].step_time_s is not None


def test_step_record_annotate_and_snapshot(tmp_path):
    rec = tl.StepRecorder("r", directory=str(tmp_path))
    rec.record_step(0)
    rec.record_step(1)
    rec.annotate(loss=0.5, all_finite=True, skipped_steps=2)
    rec.annotate(step=0, rolled_back=True)
    assert rec.records[-1].loss == 0.5
    assert rec.records[-1].skipped_steps == 2
    assert rec.records[0].rolled_back is True
    snap = rec.snapshot()
    assert snap["step"] == 1 and snap["loss"] == 0.5


# -- event journal -----------------------------------------------------------

def test_event_journal_roundtrip(tmp_path):
    j = ev.EventJournal(directory=str(tmp_path))
    j.emit("chaos/kill", step=6, proc=1)
    j.emit("checkpoint/save", step=6, duration_s=0.25, path="/x")
    j.close()
    loaded = ev.load_run_events(str(tmp_path))
    assert [r["kind"] for r in loaded] == ["chaos/kill", "checkpoint/save"]
    assert loaded[0]["step"] == 6 and loaded[0]["pid"] == os.getpid()
    assert loaded[1]["duration_s"] == 0.25
    # merge across writers: a second "host" journal interleaves by time
    j2 = ev.EventJournal(directory=str(tmp_path), host="other-host")
    j2.emit("supervisor/attempt_start", attempt=0)
    j2.close()
    merged = ev.load_run_events(str(tmp_path))
    assert len(merged) == 3
    assert merged[-1]["kind"] == "supervisor/attempt_start"
    assert merged == sorted(merged, key=lambda r: r["time"])


def test_emit_event_process_journal(tmp_path):
    ev.configure(str(tmp_path))
    out = ev.emit_event("numerics/skip", step=3, skipped_total=1)
    assert out is not None
    assert ev.load_run_events(str(tmp_path))[0]["kind"] == "numerics/skip"
    # journal never raises on a broken directory
    ev.configure("/dev/null/not-a-dir")
    assert ev.emit_event("x") is None


# -- calibration -------------------------------------------------------------

def test_fit_constants_recovers_planted():
    bw, alpha = 2e9, 2e-4
    rng = np.random.RandomState(1)
    records = []
    for _ in range(40):
        x = float(rng.uniform(1e5, 5e7))
        n = float(rng.randint(1, 12))
        records.append({"step_time_s": x / bw + alpha * n,
                        "exposed_bytes": x, "num_collectives": n})
    fc = cal.fit_constants(records)
    assert fc.ici_bandwidth == pytest.approx(bw, rel=1e-3)
    assert fc.alpha == pytest.approx(alpha, rel=1e-3)
    assert fc.improved
    assert fc.mean_abs_error_s < fc.baseline_mean_abs_error_s


def test_fit_constants_degenerate_inputs():
    # Compute-bound: time does not grow with bytes — must clamp, not blow
    # up, and still beat the default constants on ITS records.
    records = [{"step_time_s": 0.05, "exposed_bytes": 1e6,
                "num_collectives": 2}] * 5
    fc = cal.fit_constants(records)
    assert fc is not None and fc.ici_bandwidth > 0 and fc.alpha >= 0
    assert fc.mean_abs_error_s <= fc.baseline_mean_abs_error_s
    assert cal.fit_constants([]) is None


def test_fit_constants_trims_outlier_steps():
    """A compile/trace-window hiccup (one 4 s step among 2 ms steps)
    must not dominate the fit or the drift verdict."""
    bw, alpha = 2e9, 2e-4
    rng = np.random.RandomState(2)
    records = []
    for _ in range(30):
        x = float(rng.uniform(1e5, 5e7))
        n = float(rng.randint(1, 12))
        records.append({"step_time_s": x / bw + alpha * n,
                        "exposed_bytes": x, "num_collectives": n})
    records.append({"step_time_s": 4.5, "exposed_bytes": 1e6,
                    "num_collectives": 2})      # the trace-window stall
    fc = cal.fit_constants(records)
    assert fc.ici_bandwidth == pytest.approx(bw, rel=1e-3)
    assert fc.n_records == 30                   # outlier trimmed
    pm = cal.predicted_vs_measured(
        [dict(r, predicted_step_time_s=r["step_time_s"]) for r in records])
    assert pm["drift"] is None                  # median is outlier-robust


def test_model_drift_rule():
    assert cal.model_drift_reason(0.01, 0.011) is None
    why = cal.model_drift_reason(0.001, 0.05)
    assert why is not None and "recalibrate" in why
    why = cal.model_drift_reason(0.05, 0.001)
    assert why is not None and "overprices" in why
    assert cal.model_drift_reason(None, 0.05) is None
    assert cal.model_drift_reason(0.01, None) is None


def test_model_drift_lint_fires():
    """analysis pass `telemetry`: WARN on drifted measurement provenance,
    quiet within threshold, inert without provenance."""
    from autodist_tpu.analysis import analyze
    from autodist_tpu.graph_item import GraphItem
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy import AllReduce

    params = {"w": jnp.zeros((64, 64), jnp.float32)}
    gi = GraphItem(params)
    spec = ResourceSpec(resource_info={"nodes": [
        {"address": "127.0.0.1", "chips": 8, "chief": True}]})
    strat = AllReduce().build(gi, spec)

    report = analyze(strat, gi, mesh={"data": 8},
                     telemetry={"measured_step_time_s": 0.5,
                                "predicted_step_time_s": 0.001})
    assert any(d.rule == "telemetry/model-drift" for d in report.warnings)

    report = analyze(strat, gi, mesh={"data": 8},
                     telemetry={"measured_step_time_s": 0.0011,
                                "predicted_step_time_s": 0.001})
    assert not any(d.rule.startswith("telemetry/")
                   for d in report.diagnostics)

    report = analyze(strat, gi, mesh={"data": 8})
    assert not any(d.rule.startswith("telemetry/")
                   for d in report.diagnostics)

    # missing measurement -> INFO, not WARN
    report = analyze(strat, gi, mesh={"data": 8},
                     telemetry={"measured_step_time_s": 0.5})
    assert any(d.rule == "telemetry/no-measurement"
               for d in report.diagnostics)
    assert not any(d.rule == "telemetry/model-drift"
                   for d in report.diagnostics)


# -- session / fit integration ----------------------------------------------

@pytest.fixture(scope="module")
def session():
    from autodist_tpu.autodist import AutoDist, \
        _reset_default_autodist_for_testing
    from autodist_tpu.strategy import Zero1

    _reset_default_autodist_for_testing()
    rng = np.random.RandomState(0)
    params = {"l1": {"w": jnp.asarray(rng.randn(64, 64) * 0.05,
                                      jnp.float32)},
              "out": {"w": jnp.asarray(rng.randn(64, 1) * 0.1,
                                       jnp.float32)}}
    batch = {"x": rng.randn(32, 64).astype(np.float32),
             "y": rng.randn(32).astype(np.float32)}

    def loss_fn(p, b):
        h = jnp.tanh(b["x"] @ p["l1"]["w"])
        return jnp.mean(((h @ p["out"]["w"])[:, 0] - b["y"]) ** 2)

    ad = AutoDist(strategy_builder=Zero1(bucket_bytes=256 << 10))
    with ad.scope():
        ad.capture(params=params, optimizer=optax.adam(1e-3),
                   loss_fn=loss_fn)
    sess = ad.create_distributed_session()
    yield sess, batch
    _reset_default_autodist_for_testing()


def test_session_records_steps_with_prediction(session):
    sess, batch = session
    for _ in range(5):
        sess.run(batch, sync=False)
    rec = sess.telemetry
    assert rec is not None
    records = rec.records
    assert len(records) >= 5
    last = records[-1]
    assert last.step == sess.step_count - 1
    assert last.step_time_s is not None and last.step_time_s > 0
    assert last.phases.get("dispatch", 0) > 0
    assert last.items_per_s and last.items_per_s > 0
    # the calibration bridge: every record carries the cost model's
    # prediction for the active (ZeRO-1) strategy
    assert last.sync_bytes and last.exposed_bytes
    assert last.exposed_bytes < last.sync_bytes   # prefetch hides AG half
    assert last.num_collectives and last.predicted_step_time_s
    snap = rec.snapshot()
    assert snap["step"] == last.step and "step_time_ms" in snap


def test_calibration_improves_on_recorded_run(session):
    """Acceptance: fit_constants() on a recorded run reduces the cost
    model's step-time prediction error on that run versus the default
    (uncalibrated) constants."""
    sess, batch = session
    for _ in range(10):
        sess.run(batch, sync=False)
    records = sess.telemetry.records
    fc = cal.fit_constants(records)
    assert fc is not None and fc.n_records > 0
    assert fc.mean_abs_error_s <= fc.baseline_mean_abs_error_s
    err_default = cal.prediction_error(records)
    err_fitted = cal.prediction_error(records, **fc.as_cost_kwargs())
    assert err_fitted <= err_default


def test_fit_adds_phases_and_loss(session):
    sess, batch = session
    hist = sess.fit([batch] * 6, epochs=1, log_every=2)
    assert hist.steps_run == 6
    records = sess.telemetry.records
    assert any("data_load" in r.phases for r in records)
    # log_every fetches annotate the loss onto the fetched step's record
    assert any(r.loss is not None for r in records)


def test_heartbeat_carries_step_snapshot(tmp_path, session):
    from autodist_tpu.resilience.heartbeat import (
        HeartbeatCallback,
        HeartbeatMonitor,
        HeartbeatWriter,
        WEDGED,
    )

    sess, batch = session
    writer = HeartbeatWriter(str(tmp_path), "worker0", interval=60.0)
    cb = HeartbeatCallback(writer)
    sess.fit([batch] * 3, epochs=1, callbacks=[cb])

    monitor = HeartbeatMonitor(str(tmp_path), timeout=30.0)
    health = monitor.check("worker0")
    assert health.snapshot is not None
    assert health.snapshot["step"] == sess.step_count - 1
    assert "step_time_ms" in health.snapshot

    # a stale beacon (process alive) is WEDGED — and the verdict still
    # says what the worker was doing, plus journals the transition once
    ev.configure(None)
    stale = HeartbeatMonitor(str(tmp_path), timeout=0.0)
    time.sleep(0.05)
    bad = stale.failures()
    assert bad["worker0"].state == WEDGED
    # The flight-recorder cursor leads the doing() rendering when the
    # beacon carries one (PR 15); the snapshot string is the fallback
    # (tests/test_flightrec.py covers both).
    doing = bad["worker0"].doing()
    assert "in phase step" in doing or "last doing: step" in doing
    verdicts = [e for e in ev.get_journal().events
                if e["kind"] == "heartbeat/verdict"]
    assert len(verdicts) == 1 and verdicts[0]["state"] == WEDGED
    stale.failures()   # second poll: same state, no duplicate event
    verdicts = [e for e in ev.get_journal().events
                if e["kind"] == "heartbeat/verdict"]
    assert len(verdicts) == 1


def test_step_records_flush_to_env_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("AUTODIST_TELEMETRY_DIR", str(tmp_path))
    from autodist_tpu.autodist import AutoDist, \
        _reset_default_autodist_for_testing
    from autodist_tpu.strategy import AllReduce

    _reset_default_autodist_for_testing()
    params = {"w": jnp.zeros((32, 32), jnp.float32)}
    batch = {"x": np.ones((16, 32), np.float32)}

    def loss_fn(p, b):
        return jnp.mean((b["x"] @ p["w"]) ** 2)

    ad = AutoDist(strategy_builder=AllReduce(bucket_bytes=64 << 10))
    with ad.scope():
        ad.capture(params=params, optimizer=optax.sgd(0.1),
                   loss_fn=loss_fn)
    sess = ad.create_distributed_session()
    for _ in range(3):
        sess.run(batch, sync=False)
    sess.telemetry.flush()
    loaded = tl.load_step_records(str(tmp_path))
    assert len(loaded) == 3
    _reset_default_autodist_for_testing()


# -- re-armable trace windows (AUTODIST_TRACE_AT) ---------------------------

def test_trace_at_opens_midrun_windows(tmp_path, monkeypatch):
    """AUTODIST_TRACE_AT=<steps> opens capture windows MID-RUN (the old
    tracer could only capture steps 0..N-1), one subdirectory per
    window, never overlapping."""
    monkeypatch.setenv("AUTODIST_IS_TESTING", "True")
    monkeypatch.setenv("AUTODIST_TRACE_STEPS", "1")
    monkeypatch.setenv("AUTODIST_TRACE_AT", "2,4")
    from autodist_tpu.utils import tracing as tr
    monkeypatch.setattr(tr, "DEFAULT_TRACE_DIR", str(tmp_path / "traces"))

    from autodist_tpu.autodist import AutoDist, \
        _reset_default_autodist_for_testing

    _reset_default_autodist_for_testing()
    params = {"w": jnp.zeros((16, 16), jnp.float32)}
    batch = {"x": np.ones((8, 16), np.float32)}

    def loss_fn(p, b):
        return jnp.mean((b["x"] @ p["w"]) ** 2)

    ad = AutoDist(mesh_axes={"data": 8})
    with ad.scope():
        ad.capture(params=params, optimizer=optax.sgd(0.1),
                   loss_fn=loss_fn)
    sess = ad.create_distributed_session()
    for _ in range(6):
        sess.run(batch)
    tr.flush_active_trace()
    run_dirs = list((tmp_path / "traces").iterdir())
    assert len(run_dirs) == 1
    windows = sorted(p.name for p in run_dirs[0].iterdir())
    assert windows == ["step2", "step4"]
    for w in run_dirs[0].iterdir():
        files = [f for f in w.rglob("*") if f.is_file()]
        assert files, f"window {w} wrote no trace"
    _reset_default_autodist_for_testing()


def test_trace_at_parse_errors():
    from autodist_tpu.utils.tracing import _parse_trace_at

    assert _parse_trace_at("") == ()
    assert _parse_trace_at("4, 2,4") == (2, 4)
    with pytest.raises(ValueError, match="AUTODIST_TRACE_AT"):
        _parse_trace_at("two")


# -- serving /metrics --------------------------------------------------------

@pytest.mark.slow
def test_metrics_endpoint_smoke():
    import http.client

    from autodist_tpu.models.transformer import dense_attention
    from autodist_tpu.models.transformer_lm import transformer_lm
    from autodist_tpu.serving import DecodeEngine, EngineServer

    spec = transformer_lm(vocab_size=61, num_layers=1, num_heads=2,
                          head_dim=8, d_ff=32, max_len=48, seq_len=16,
                          attn_fn=dense_attention)
    params = spec.init(jax.random.PRNGKey(0))
    eng = DecodeEngine(spec, params, slots=2, window=24, chunk=4)
    srv = EngineServer(eng, port=0, request_timeout_s=120).start()
    try:
        conn = http.client.HTTPConnection(*srv.address, timeout=120)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt_tokens": [1, 2, 3],
                                 "max_new_tokens": 4}),
                     {"Content-Type": "application/json"})
        assert conn.getresponse().status == 200
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Content-Type").startswith("text/plain")
        text = resp.read().decode()
        assert "# TYPE autodist_serving_request_latency_seconds " \
               "histogram" in text
        assert "autodist_serving_request_latency_seconds_count 1" in text
        assert "autodist_serving_requests_served_total 1" in text
        assert "# TYPE autodist_serving_queue_depth histogram" in text
        conn.request("GET", "/v1/stats")
        st = json.loads(conn.getresponse().read())
        assert st["requests_served"] == 1
        assert st["latency_p50_ms"] > 0
        conn.close()
    finally:
        srv.close()


# -- CLI ---------------------------------------------------------------------

def _make_run_dir(tmp_path) -> str:
    run = tmp_path / "run"
    rec = tl.StepRecorder(
        "r", directory=str(run), flush_every=1,
        predictor=lambda: {"time_s": 2e-3, "wire_bytes": 3e6,
                           "exposed_wire_bytes": 2e6,
                           "num_collectives": 4})
    for i in range(20):
        rec.add_phase("data_load", 0.001)
        rec.add_phase("dispatch", 0.002)
        rec.record_step(i, items=8)
        time.sleep(0.001)
    rec.annotate(loss=0.25, all_finite=True)
    rec.flush()
    j = ev.EventJournal(directory=str(run))
    j.emit("checkpoint/save", step=19, duration_s=0.1, path="/ckpt")
    j.emit("supervisor/attempt_start", attempt=0)
    j.close()
    return str(run)


def test_cli_summarizes_run_dir(tmp_path, capsys):
    from autodist_tpu.telemetry.__main__ import main

    run = _make_run_dir(tmp_path)
    assert main([run, "--fit"]) == 0
    out = capsys.readouterr().out
    assert "steps: 20" in out
    assert "phase data_load" in out
    assert "events (2 total" in out
    assert "checkpoint/save" in out
    assert "calibrated:" in out
    # machine mode round-trips as one JSON object
    assert main([run, "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["steps"] == 20
    assert len(payload["events"]) == 2
    # empty dir exits 2
    assert main([str(tmp_path / "empty")]) == 2


def test_cli_subprocess_smoke(tmp_path):
    """CI smoke: the module entry point runs jax-free on a fixture run
    dir and exits 0."""
    run = _make_run_dir(tmp_path)
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "autodist_tpu.telemetry", run],
        cwd="/root/repo", env=env, capture_output=True, timeout=120)
    assert proc.returncode == 0, proc.stderr.decode()
    assert b"telemetry summary" in proc.stdout


# -- host spans: one mechanism, two clocks, one ring ------------------------

from autodist_tpu.telemetry import profiler as prof  # noqa: E402


@pytest.fixture
def span_ring():
    """An empty process span ring, and the registry's compile counters
    read from zero."""
    prof.reset_spans_for_testing()
    yield prof.get_span_writer()
    prof.reset_spans_for_testing()


def _fresh_session(rows=32, builder=None):
    """A new AutoDist and session (step counter 0) on the 8-device mesh,
    and a batch of ``rows`` rows for it."""
    from autodist_tpu.autodist import AutoDist, \
        _reset_default_autodist_for_testing

    _reset_default_autodist_for_testing()
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(16, 16) * 0.1, jnp.float32)}
    batch = {"x": rng.randn(rows, 16).astype(np.float32),
             "ids": np.zeros((rows, 4), np.int32)}

    def loss_fn(p, b):
        return jnp.mean((b["x"] @ p["w"]) ** 2)

    ad = AutoDist(strategy_builder=builder)
    with ad.scope():
        ad.capture(params=params, optimizer=optax.sgd(0.1),
                   loss_fn=loss_fn)
    return ad, batch


def _inside(child, parent):
    return parent["start"] <= child["start"] and child["end"] <= parent["end"]


def test_session_run_spans_share_a_step(span_ring):
    """Three steps: three ``session/run`` spans, each holding its four
    children in order, all five carrying the same ``step``."""
    ad, batch = _fresh_session()
    sess = ad.create_distributed_session()
    for _ in range(3):
        sess.run(batch)
    spans = span_ring.spans
    runs = [s for s in spans if s["name"] == tl.SESSION_RUN]
    assert [s["ids"]["step"] for s in runs] == [0, 1, 2]
    order = [tl.SESSION_PLACE_BATCH, tl.SESSION_ENQUEUE,
             tl.SESSION_RECORD, tl.SESSION_FETCH]
    for run in runs:
        kids = [s for s in spans if s["parent"] == tl.SESSION_RUN
                and s["ids"].get("step") == run["ids"]["step"]]
        assert [k["name"] for k in kids] == order
        assert all(_inside(k, run) for k in kids)
        assert all(a["end"] <= b["start"] for a, b in zip(kids, kids[1:]))
        assert run["parent"] is None
    # an async step waits for nothing: no fetch span
    sess.run(batch, sync=False)
    last = [s["name"] for s in span_ring.spans if s["ids"].get("step") == 3]
    assert tl.SESSION_FETCH not in last and tl.SESSION_RUN in last


def test_step_phases_come_from_the_span_clock(span_ring):
    """``dispatch`` keeps its extent (entry to after the meter), so its
    two named parts fit inside it; ``fetch`` is the wait after it."""
    ad, batch = _fresh_session()
    sess = ad.create_distributed_session()
    for _ in range(3):
        sess.run(batch)
    rec = sess.telemetry.records[-1]
    assert {"dispatch", "place_batch", "enqueue", "fetch"} <= set(rec.phases)
    assert rec.phases["place_batch"] + rec.phases["enqueue"] \
        <= rec.phases["dispatch"]
    spans = {s["name"]: s for s in span_ring.spans
             if s["ids"].get("step") == rec.step}
    for phase, name in (("place_batch", tl.SESSION_PLACE_BATCH),
                        ("enqueue", tl.SESSION_ENQUEUE),
                        ("fetch", tl.SESSION_FETCH)):
        assert rec.phases[phase] == pytest.approx(
            spans[name]["end"] - spans[name]["start"])
    run = spans[tl.SESSION_RUN]
    assert rec.phases["dispatch"] <= spans[tl.SESSION_RECORD]["end"] \
        - run["start"]
    assert rec.phases["dispatch"] + rec.phases["fetch"] \
        <= run["end"] - run["start"]


def test_telemetry_off_leaves_the_ring_empty(span_ring, monkeypatch):
    monkeypatch.setenv("AUTODIST_TELEMETRY", "0")
    assert tl.host_span(tl.SESSION_RUN, step=0) is tl._NULL_SPAN
    with tl.host_span("anything") as span:
        assert span is None
    ad, batch = _fresh_session()
    sess = ad.create_distributed_session()
    out = sess.run(batch)
    assert np.isfinite(out["loss"]) and sess.telemetry is None
    assert prof.record_span("request", start=0.0, end=1.0) is None
    assert span_ring.spans == []


def test_setup_spans_lie_inside_session_creation(span_ring):
    """Every ``setup/*`` span falls between entry to and return from
    ``create_distributed_session``, none overlaps another, and the cost
    model is priced once: the same report is logged and stamped."""
    from autodist_tpu.strategy import Zero1
    from autodist_tpu.strategy import cost_model

    ad, batch = _fresh_session(builder=Zero1(bucket_bytes=256 << 10))
    calls = []
    real = cost_model.estimate_cost
    cost_model.estimate_cost = lambda *a, **k: calls.append(1) or real(*a, **k)
    try:
        t0 = time.perf_counter()
        sess = ad.create_distributed_session(validate=True)
        t1 = time.perf_counter()
        sess.run(batch)
    finally:
        cost_model.estimate_cost = real
    setup = sorted((s for s in span_ring.spans
                    if s["name"].startswith("setup/")),
                   key=lambda s: s["start"])
    assert [s["name"] for s in setup] == [
        tl.SETUP_BUILD_STRATEGY, tl.SETUP_COMPILE_STRATEGY,
        tl.SETUP_PREFLIGHT, tl.SETUP_TRANSFORM, tl.SETUP_PLACE_PARAMS,
        tl.SETUP_INIT_OPT_STATE, tl.SETUP_INIT_SYNC_STATE,
        tl.SETUP_ESTIMATE_COST]
    assert t0 <= setup[0]["start"] and setup[-1]["end"] <= t1
    assert all(a["end"] <= b["start"] for a, b in zip(setup, setup[1:]))
    assert len(calls) == 1
    assert sess.telemetry.records[-1].predicted_step_time_s \
        == sess.cost_report.time_s


def test_shape_change_at_step_2_is_a_journalled_recompile(span_ring):
    """Compiling at step 0 is set-up; a new batch shape at step 2 makes
    jax compile again inside ``session/run``: one more on
    ``autodist_recompiles_total``, and the journal says which step."""
    reg.reset_for_testing()
    ad, batch = _fresh_session()
    sess = ad.create_distributed_session()
    sess.run(batch)
    sess.run(batch)

    def recompiles():
        return sum(m.value for m in reg.DEFAULT_REGISTRY.metrics()
                   if m.name == "autodist_recompiles_total")

    assert recompiles() == 0
    assert sess._batch_sizes == (32, 32 * 4)
    sess.run({k: v[:16] for k, v in batch.items()})
    assert recompiles() == 1
    assert sess._batch_sizes == (16, 16 * 4)
    events = [e for e in ev.get_journal().events
              if e["kind"] == "compile/recompile"]
    assert len(events) == 1 and events[0]["step"] == 2
    assert events[0]["span"] == tl.SESSION_RUN
    assert "step" in events[0]["fun_name"]
    backend = [s for s in span_ring.spans if s["name"] == tl.COMPILE_BACKEND
               and s["parent"] == tl.SESSION_ENQUEUE]
    assert len(backend) == 2          # step 0's and step 2's
    stages = {m.labels["stage"]: m.value
              for m in reg.DEFAULT_REGISTRY.metrics()
              if m.name == "autodist_compile_seconds_total"}
    assert set(stages) == {"trace", "lower", "backend"}
    assert 'autodist_compile_seconds_total{stage="trace"}' \
        in reg.render_prometheus()


def test_nested_traces_count_their_own_seconds(span_ring, monkeypatch):
    """jax times a jitted function traced from inside another within
    the outer one's seconds too: the counter takes each trace's own
    part, the spans keep their extent, events under a millisecond stay
    out of the ring."""
    reg.reset_for_testing()
    base = 1e9      # later than any trace this thread really remembers
    clock = [base]
    monkeypatch.setattr(tl.time, "perf_counter", lambda: clock[0])
    event = "/jax/core/compile/jaxpr_trace_duration"
    clock[0] = base + 0.4
    tl._on_compile_event(event, 0.1, fun_name="inner")      # [.3, .4]
    clock[0] = base + 0.5
    tl._on_compile_event(event, 0.0001, fun_name="tiny")    # in outer too
    clock[0] = base + 1.0
    tl._on_compile_event(event, 0.9, fun_name="outer")      # [.1, 1.0]
    clock[0] = base + 2.0
    tl._on_compile_event(event, 0.5, fun_name="next")       # a sibling
    tl._on_compile_event("/jax/some/other/event", 9.0)
    trace = reg.counter("autodist_compile_seconds_total",
                        labels={"stage": "trace"})
    assert trace.value == pytest.approx(0.9 + 0.5, abs=1e-5)
    names = [s["ids"]["fun_name"] for s in span_ring.spans]
    assert names == ["inner", "outer", "next"]
    outer = span_ring.spans[1]
    assert outer["end"] - outer["start"] == pytest.approx(0.9, abs=1e-5)


def test_host_span_and_record_span_write_one_record(span_ring, tmp_path):
    """The live and the after-the-fact form: the same keys, the same
    ring, both clocks on both, the same JSONL under a run directory."""
    ring = prof.configure_spans(str(tmp_path))
    with tl.host_span("outer", request_id=7):
        with tl.host_span("inner", request_id=7) as live:
            pass
    t = time.perf_counter()
    prof.record_span("after", start=t - 0.25, end=t, trace_id="t1",
                     request_id=7)
    prof.record_span("walled", start_unix=time.time() - 1.0, dur_s=0.5)
    inner, outer, after, walled = ring.spans
    assert set(inner) == set(outer) == set(after) == set(walled)
    assert inner["parent"] == "outer" and outer["parent"] is None
    assert after["parent"] is None and after["trace_id"] == "t1"
    assert inner["ids"] == outer["ids"] == after["ids"] == {"request_id": 7}
    assert (inner["start"], inner["end"]) == (live.start, live.end)
    for s in ring.spans:
        assert s["dur_s"] == pytest.approx(s["end"] - s["start"])
        assert abs(s["start_unix"] - time.time()) < 60
    assert walled["end"] - walled["start"] == pytest.approx(0.5)
    assert time.perf_counter() - walled["start"] == pytest.approx(1.0,
                                                                  abs=0.2)
    ring.close()
    assert sorted(prof.load_spans(str(tmp_path)), key=lambda s: s["name"]) \
        == sorted(ring.spans, key=lambda s: s["name"])


def test_host_span_parent_is_per_thread(span_ring):
    """``parent`` is the enclosing span of the SAME thread: a span opened
    on another thread while this one holds ``outer`` has no parent."""
    import threading

    def other():
        with tl.host_span("elsewhere"):
            pass

    with tl.host_span("outer"):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        with tl.host_span("inner"):
            pass
    by = {s["name"]: s for s in span_ring.spans}
    assert by["elsewhere"]["parent"] is None
    assert by["inner"]["parent"] == "outer"


def test_host_spans_land_on_the_profilers_host_plane(span_ring, tmp_path):
    """In a capture window the spans are ``autodist/...`` events on
    ``/host:CPU`` of the ``.xplane.pb``, their ids as stats: the clock
    the device planes are on."""
    from jax.profiler import ProfileData

    ad, batch = _fresh_session()
    sess = ad.create_distributed_session()
    sess.run(batch)
    jax.profiler.start_trace(str(tmp_path))
    try:
        sess.run(batch)
        sess.run(batch)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    events = [(e.name, dict(e.stats))
              for plane in ProfileData.from_file(str(path)).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events
              if e.name.startswith(tl.SPAN_PREFIX)]
    for name in (tl.SESSION_RUN, tl.SESSION_PLACE_BATCH,
                 tl.SESSION_ENQUEUE, tl.SESSION_RECORD, tl.SESSION_FETCH):
        steps = [ids["step"] for n, ids in events
                 if n == tl.SPAN_PREFIX + name]
        assert steps == [1, 2], (name, events)


def test_evaluate_and_prefetch_place_batches_under_a_span(span_ring):
    ad, batch = _fresh_session()
    sess = ad.create_distributed_session()
    sess.evaluate([batch, batch])
    placed = list(sess.prefetch([batch, batch, batch]))
    assert len(placed) == 3
    names = [s["name"] for s in span_ring.spans
             if s["name"].startswith("session/")]
    assert names == [tl.SESSION_PLACE_BATCH] * 5


def test_last_batch_shapes_rebuilt_only_on_change(span_ring):
    """The shape tree for on-demand FLOPs and the (items, tokens) pair
    are rebuilt when a leaf's shape or dtype changes, not every step."""
    ad, batch = _fresh_session()
    sess = ad.create_distributed_session()
    sess.run(batch)
    tree = sess._last_batch
    assert tree["x"].shape == (32, 16) and tree["ids"].dtype == np.int32
    sess.run(batch)
    assert sess._last_batch is tree
    sess.run({k: v[:8] for k, v in batch.items()})
    assert sess._last_batch is not tree
    assert sess._last_batch["x"].shape == (8, 16)
    rec = sess.telemetry.records[-1]
    assert rec.step_time_s and rec.items_per_s == pytest.approx(
        8 / rec.step_time_s)


def test_engine_tick_and_sse_poll_spans(span_ring):
    """A tiny paged engine behind the HTTP front, a few requests, one of
    them streamed: ``engine/step`` holds its children (harvest, admit,
    prefill with rows and bucket, decode chunk with n, the host sync),
    the driver's lock wait and the observer are there, and a stream's
    poll carries its ``request_id``.  A dispatch shape that ran before
    says so (``seen``), which is what makes a compile a recompile."""
    import http.client

    from autodist_tpu.models.transformer import dense_attention
    from autodist_tpu.models.transformer_lm import transformer_lm
    from autodist_tpu.serving import EngineServer, PagedDecodeEngine

    spec = transformer_lm(vocab_size=61, num_layers=2, num_heads=2,
                          head_dim=8, d_ff=32, max_len=48, seq_len=16,
                          attn_fn=dense_attention)
    eng = PagedDecodeEngine(spec, spec.init(jax.random.PRNGKey(0)),
                            slots=2, window=32, block_size=8,
                            num_blocks=24, chunk=4)
    srv = EngineServer(eng, port=0, request_timeout_s=120).start()
    try:
        for body in ({"prompt_tokens": [3, 5, 7], "max_new_tokens": 9},
                     {"prompt_tokens": [2, 4, 6], "max_new_tokens": 9,
                      "stream": True}):
            conn = http.client.HTTPConnection(*srv.address, timeout=120)
            conn.request("POST", "/v1/completions", json.dumps(body),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            resp.read()
            conn.close()
    finally:
        srv.close()
    spans = span_ring.spans
    steps = [s for s in spans if s["name"] == tl.ENGINE_STEP]
    assert steps
    # (in a fresh process jax also compiles under these: compile/* records)
    kids = [s for s in spans if s["parent"] == tl.ENGINE_STEP
            and s["name"].startswith("engine/")]
    assert {k["name"] for k in kids} == {
        tl.ENGINE_HARVEST, tl.ENGINE_ADMIT, tl.ENGINE_PREFILL,
        tl.ENGINE_DECODE_CHUNK, tl.ENGINE_HOST_SYNC}
    assert all(any(_inside(k, s) for s in steps) for k in kids)
    prefills = [k["ids"] for k in kids if k["name"] == tl.ENGINE_PREFILL]
    assert prefills[0] == {"rows": 1, "bucket": 4, "seen": 0}
    assert prefills[1] == {"rows": 1, "bucket": 4, "seen": 1}
    chunks = [k["ids"] for k in kids if k["name"] == tl.ENGINE_DECODE_CHUNK]
    assert chunks[0] == {"n": 4, "seen": 0} and chunks[-1]["seen"] > 0
    names = {s["name"] for s in spans}
    assert {tl.SERVER_LOCK_WAIT, tl.SERVER_OBSERVE} <= names
    polls = [s for s in spans if s["name"] == tl.SERVER_SSE_POLL]
    assert polls and {p["ids"]["request_id"] for p in polls} == {1}
    # the per-request record operators export rides the same ring
    life = [s["name"] for s in spans if s["ids"].get("request_id") == 1
            and s["name"] in ("queue_wait", "prefill", "decode")]
    assert sorted(life) == ["decode", "prefill", "queue_wait"]


# -- step values (telemetry/step_values.py) ------------------------------------
def _linear_session(loss_fn, **capture):
    from autodist_tpu import strategy as strategies
    from autodist_tpu.autodist import (AutoDist,
                                       _reset_default_autodist_for_testing)
    from autodist_tpu.mesh import build_mesh

    _reset_default_autodist_for_testing()
    ad = AutoDist(strategy_builder=strategies.AllReduce(),
                  mesh_axes={"data": 1})
    with ad.scope():
        ad.capture(params={"w": jnp.ones((4,))}, optimizer=optax.sgd(0.1),
                   loss_fn=loss_fn, **capture)
    return ad.create_distributed_session(
        mesh=build_mesh({"data": 1}, devices=jax.devices()[:1]))


@pytest.mark.parametrize("has_aux,accum", [(False, 1), (True, 1), (False, 2)])
def test_step_values_leave_with_the_metrics_and_reach_their_publisher(
        has_aux, accum):
    """A marked loss function's emitted value comes to its publisher as
    host numpy after every fetched step (stacked over the microbatches
    under gradient accumulation), the caller's metrics do not show it,
    the user's own aux stays what it was, and the step program holds no
    host callback (it would keep the program out of the persistent
    compilation cache)."""
    from autodist_tpu.autodist import _reset_default_autodist_for_testing
    from autodist_tpu.telemetry import step_values

    seen = []

    @step_values.reporting
    def loss_fn(params, batch):
        rows = jnp.sum(batch["x"], axis=-1)
        step_values.emit("rows", {"sum": rows.sum(), "n": rows.shape[0]},
                         seen.append)
        loss = jnp.mean((batch["x"] @ params["w"]) ** 2)
        return (loss, {"rows": rows}) if has_aux else loss

    sess = _linear_session(loss_fn, has_aux=has_aux, accum_steps=accum)
    batches = [{"x": np.full((4, 4), float(i + 1), np.float32)}
               for i in range(2)]
    outs = [sess.run(b) for b in batches]
    assert "callback" not in sess.lower_step(batches[0]).as_text().lower()
    assert step_values.KEY not in sess.evaluate(batches[0])
    assert not sess.run(batches[0], sync=False).keys() - {"loss", "aux"}
    _reset_default_autodist_for_testing()
    assert [set(o) for o in outs] == [{"loss", "aux"} if has_aux
                                      else {"loss"}] * 2
    assert len(seen) == 2                  # evaluate and sync=False: none
    for i, got in enumerate(seen):
        assert isinstance(got["sum"], np.ndarray)
        assert got["sum"].shape == got["n"].shape == (() if accum == 1
                                                      else (accum,))
        assert float(got["sum"].sum()) == 16.0 * (i + 1)
        assert int(got["n"].sum()) == 4
    if has_aux:
        np.testing.assert_array_equal(outs[1]["aux"]["rows"], [8.0] * 4)


def test_emit_outside_a_collecting_trace_does_nothing():
    """An unmarked loss function, or a marked one called or differentiated
    directly, traces and runs as if ``emit`` were not there."""
    from autodist_tpu.autodist import _reset_default_autodist_for_testing
    from autodist_tpu.telemetry import step_values

    seen = []

    def loss_fn(params, batch):
        step_values.emit("x", batch["x"].sum(), seen.append)
        return jnp.mean((batch["x"] @ params["w"]) ** 2)

    batch = {"x": np.ones((4, 4), np.float32)}
    sess = _linear_session(loss_fn)
    assert set(sess.run(batch)) == {"loss"}
    _reset_default_autodist_for_testing()
    marked = step_values.reporting(loss_fn)
    jax.jit(jax.grad(marked))({"w": jnp.ones((4,))}, batch)
    assert seen == []
