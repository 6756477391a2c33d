"""Flight recorder (docs/observability.md "Flight recorder").

Cursor-ring overwrite semantics, cursor↔beacon round-trip through the
heartbeat machinery, hang localization against planted cursor sets
(including ties and multi-host frontiers), crash-bundle round-trip +
the ``--hang-report`` CLI, chaos ``hang`` grammar, traced leg stamps
under ``AUTODIST_FLIGHTREC=legs``, and the supervisor's
bundle-on-failure wiring.  The live 2-process wedge drill is the slow
test at the bottom (``tests/integration/hang_drill.py``).
"""
import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.telemetry import events as ev
from autodist_tpu.telemetry import flightrec as fr

pytestmark = pytest.mark.flightrec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("AUTODIST_TELEMETRY", raising=False)
    monkeypatch.delenv("AUTODIST_TELEMETRY_DIR", raising=False)
    monkeypatch.delenv("AUTODIST_FLIGHTREC", raising=False)
    fr.reset_for_testing()
    ev.reset_for_testing()
    yield
    fr.reset_for_testing()
    ev.reset_for_testing()


# -- cursor ring -------------------------------------------------------------

def test_ring_overwrite_semantics():
    ring = fr.CursorRing(capacity=4)
    for i in range(10):
        ring.record(fr.Cursor(leg=f"leg{i}"))
    assert ring.seq == 10
    kept = ring.cursors()
    assert [c.leg for c in kept] == ["leg6", "leg7", "leg8", "leg9"]
    assert [c.seq for c in kept] == [6, 7, 8, 9]
    assert ring.latest().leg == "leg9"
    # partial fill keeps insertion order too
    ring2 = fr.CursorRing(capacity=8)
    ring2.record(fr.Cursor(leg="a"))
    ring2.record(fr.Cursor(leg="b"))
    assert [c.leg for c in ring2.cursors()] == ["a", "b"]
    assert ring2.latest().leg == "b"


def test_record_cursor_and_dump_roundtrip(tmp_path):
    fr.set_fingerprint("fp123")
    cur = fr.record_cursor("rs:f32:0@2/reduce", slot=2, step=7,
                           leg_kind="reduce_scatter")
    assert cur is not None and cur.fingerprint == "fp123"
    path = fr.ring().dump(str(tmp_path / "c.jsonl"))
    loaded = fr.load_cursors(path)
    assert len(loaded) == 1
    assert loaded[0].leg == "rs:f32:0@2/reduce"
    assert loaded[0].slot == 2 and loaded[0].step == 7
    assert loaded[0].leg_kind == "reduce_scatter"


def test_disabled_records_nothing(monkeypatch):
    monkeypatch.setenv("AUTODIST_FLIGHTREC", "0")
    assert fr.record_cursor("x") is None
    assert fr.ring().seq == 0
    monkeypatch.setenv("AUTODIST_FLIGHTREC", "")
    monkeypatch.setenv("AUTODIST_TELEMETRY", "0")
    assert fr.record_cursor("x") is None


def test_cursor_line_rendering():
    cur = {"leg": "rs:f32:0", "kind": "leg", "leg_kind":
           "ring_reduce_scatter", "slot": 2, "age_s": 40.0}
    line = fr.cursor_line(cur, extra_age_s=1.0)
    assert line == "in ring_reduce_scatter leg rs:f32:0 slot 2 for 41 s"
    assert fr.cursor_line({"leg": "step", "kind": "phase", "age_s": 3.0,
                           "step": 9}) == "in phase step (step 9) for 3 s"
    assert fr.cursor_line(None) == ""


# -- beacon round-trip -------------------------------------------------------

def test_cursor_beacon_roundtrip(tmp_path):
    from autodist_tpu.resilience.heartbeat import (
        HeartbeatMonitor,
        HeartbeatWriter,
    )

    fr.set_fingerprint("fpabc")
    fr.record_cursor("ag:bucket@gather", slot=fr.END_OF_STEP, step=12,
                     leg_kind="all_gather")
    writer = HeartbeatWriter(str(tmp_path), "w0", interval=60.0)
    writer.beat(step=12)
    health = HeartbeatMonitor(str(tmp_path), timeout=30.0).check("w0")
    assert health.cursor is not None
    assert health.cursor["leg"] == "ag:bucket@gather"
    assert health.cursor["fingerprint"] == "fpabc"
    assert health.cursor["age_s"] >= 0.0
    assert "in all_gather leg ag:bucket@gather" in health.doing()

    # WEDGED verdict events carry the cursor
    ev.configure(None)
    stale = HeartbeatMonitor(str(tmp_path), timeout=0.0)
    time.sleep(0.05)
    bad = stale.failures()
    assert bad["w0"].state == "wedged"
    verdicts = [e for e in ev.get_journal().events
                if e["kind"] == "heartbeat/verdict"]
    assert len(verdicts) == 1
    assert verdicts[0]["cursor"]["leg"] == "ag:bucket@gather"


def test_doing_falls_back_to_snapshot():
    from autodist_tpu.resilience.heartbeat import WorkerHealth

    h = WorkerHealth("w", "alive", snapshot={"step": 3, "loss": 0.5})
    assert "last doing: step 3" in h.doing()
    h2 = WorkerHealth("w", "alive",
                      cursor={"leg": "x@0/reduce", "kind": "leg",
                              "slot": 0, "age_s": 1.0},
                      snapshot={"step": 3})
    assert "in leg x@0/reduce" in h2.doing()


# -- hang localization -------------------------------------------------------

def _legs(*specs):
    """Hand-built leg dicts: ("id", deps...)"""
    return [{"id": s[0], "deps": list(s[1:]), "kind": "all_reduce"}
            for s in specs]


CHAIN = _legs(("A",), ("B", "A"), ("C", "B"))


def test_localize_unique_culprit():
    diag = fr.localize_hang(
        {"legs": CHAIN},
        {"h0": {"leg": "A", "kind": "leg"},
         "h1": {"leg": "C", "kind": "leg"},
         "h2": {"leg": "C", "kind": "leg"}})
    assert diag is not None and not diag.tie
    assert diag.frontier_leg == "A"
    assert diag.culprits == ("h0",)
    assert "h0" in diag.detail and "A" in diag.detail


def test_localize_tie_all_same_leg():
    diag = fr.localize_hang(
        {"legs": CHAIN},
        {"h0": {"leg": "C"}, "h1": {"leg": "C"}})
    assert diag.tie
    assert diag.frontier_leg == "C"
    assert diag.culprits == ("h0", "h1")
    assert "no unique culprit" in diag.detail


def test_localize_multi_host_frontier():
    # diamond: A and B are mutually unordered, both feed C
    legs = _legs(("A",), ("B",), ("C", "A", "B"))
    diag = fr.localize_hang(
        {"legs": legs},
        {"h0": {"leg": "A"}, "h1": {"leg": "B"}, "h2": {"leg": "C"}})
    assert not diag.tie
    assert set(diag.frontier_legs) == {"A", "B"}
    assert diag.culprits == ("h0", "h1")


def test_localize_step_mismatch_wins():
    diag = fr.localize_hang(
        {"legs": CHAIN},
        {"h0": {"leg": "C", "step": 4},
         "h1": {"leg": "A", "step": 5}})
    assert diag.culprits == ("h0",)
    assert "step 4" in diag.detail and "step 5" in diag.detail


def test_localize_unknown_legs_and_empty():
    assert fr.localize_hang({"legs": CHAIN}, {}) is None
    assert fr.localize_hang({"legs": CHAIN}, {"h0": None}) is None
    diag = fr.localize_hang({"legs": CHAIN},
                            {"h0": {"leg": "step", "kind": "phase"},
                             "h1": {"leg": "step", "kind": "phase"}})
    assert diag.tie and diag.frontier_leg is None


def test_pure_fallback_matches_dataflow_reachability():
    """The jax-free ancestor-set fallback and analysis.dataflow's
    packed-bitset HappensBefore must agree on every ordered pair."""
    legs = _legs(("A",), ("B", "A"), ("C", "A"), ("D", "B", "C"),
                 ("E",), ("F", "E", "D"))
    views = fr.leg_views(legs)
    order = fr._topo(views)
    pure = fr._PureReach(views, order)
    from autodist_tpu.analysis.dataflow import HappensBefore

    hb = HappensBefore(views, order)
    ids = [v.id for v in views]
    for a in ids:
        for b in ids:
            assert pure.reaches(a, b) == hb.reaches(a, b), (a, b)


def test_localize_against_real_session_ir():
    """Planted per-host cursors over a REAL session's schedule IR: the
    host stuck at the reduce leg is the culprit; hosts at the gather
    depend on it."""
    from autodist_tpu.autodist import AutoDist, \
        _reset_default_autodist_for_testing
    from autodist_tpu.strategy import Zero1

    _reset_default_autodist_for_testing()
    params = {"l": {"w": jnp.zeros((64, 64), jnp.float32)}}

    def loss_fn(p, b):
        return jnp.mean((b["x"] @ p["l"]["w"]) ** 2)

    ad = AutoDist(strategy_builder=Zero1(bucket_bytes=256 << 10))
    with ad.scope():
        ad.capture(params=params, optimizer=optax.adam(1e-3),
                   loss_fn=loss_fn)
    sess = ad.create_distributed_session()
    ir = sess.schedule_ir
    reduce_leg = next(l.id for l in ir.legs
                      if l.kind == "reduce_scatter")
    gather_leg = next(l.id for l in ir.legs if l.kind == "all_gather")
    diag = fr.localize_hang(ir, {
        "h0": {"leg": reduce_leg, "kind": "leg"},
        "h1": {"leg": gather_leg, "kind": "leg"},
        "h2": {"leg": gather_leg, "kind": "leg"}})
    assert diag.culprits == ("h0",)
    assert diag.frontier_leg == reduce_leg
    _reset_default_autodist_for_testing()


# -- traced leg stamps -------------------------------------------------------

def test_traced_leg_stamps_hit_ir_leg_ids(monkeypatch):
    monkeypatch.setenv("AUTODIST_FLIGHTREC", "legs")
    from autodist_tpu.autodist import AutoDist, \
        _reset_default_autodist_for_testing
    from autodist_tpu.strategy import Zero1

    _reset_default_autodist_for_testing()
    rng = np.random.RandomState(0)
    params = {f"l{i}": {"w": jnp.asarray(rng.randn(64, 64) * 0.05,
                                         jnp.float32)} for i in range(2)}
    batch = {"x": rng.randn(16, 64).astype(np.float32)}

    def loss_fn(p, b):
        h = b["x"]
        for i in range(2):
            h = jnp.tanh(h @ p[f"l{i}"]["w"])
        return jnp.mean(h ** 2)

    ad = AutoDist(strategy_builder=Zero1(bucket_bytes=256 << 10))
    with ad.scope():
        ad.capture(params=params, optimizer=optax.adam(1e-3),
                   loss_fn=loss_fn)
    sess = ad.create_distributed_session()
    sess.run(batch)
    leg_ids = {l.id for l in sess.schedule_ir.legs}
    seen = {c.leg for c in fr.ring().cursors() if c.kind == "leg"}
    assert seen, "legs mode must stamp leg cursors"
    assert seen <= leg_ids
    # reduce, update, and gather groups all stamped
    assert any("reduce" in s for s in seen)
    assert any(s.startswith("update/") for s in seen)
    assert any("@gather" in s for s in seen)
    # the session stamped the fingerprint onto every cursor
    fp = sess.schedule_ir.fingerprint()
    assert all(c.fingerprint == fp for c in fr.ring().cursors()
               if c.kind == "leg")
    _reset_default_autodist_for_testing()


def test_default_mode_compiles_no_callbacks_on_cpu():
    assert fr.trace_stamps_enabled() is False   # auto == host off-TPU


# -- chaos hang --------------------------------------------------------------

def test_chaos_hang_parses_and_blocks():
    from autodist_tpu.resilience.chaos import ChaosMonkey, parse_chaos

    events = parse_chaos("hang@step=3,proc=1,leg=g0@-1/reduce,seconds=0.3")
    assert len(events) == 1
    e = events[0]
    assert e.action == "hang" and e.step == 3 and e.proc == 1
    assert e.args["leg"] == "g0@-1/reduce"

    ev.configure(None)
    monkey = ChaosMonkey(events, process_index=1, attempt=0)
    t0 = time.monotonic()
    monkey.on_step(3)
    blocked = time.monotonic() - t0
    assert blocked >= 0.25, "hang must block inside the step"
    # journaled BEFORE firing, like every chaos event
    kinds = [e["kind"] for e in ev.get_journal().events]
    assert "chaos/hang" in kinds
    # the planted cursor names the leg (what localization keys on)
    cur = fr.latest_cursor()
    assert cur is not None and cur.leg == "g0@-1/reduce"
    assert cur.kind == "leg" and cur.step == 3
    # fires at most once
    monkey.on_step(4)
    assert fr.ring().seq == 1


def test_chaos_hang_wrong_proc_does_not_fire():
    from autodist_tpu.resilience.chaos import ChaosMonkey, parse_chaos

    monkey = ChaosMonkey(parse_chaos("hang@step=3,proc=1,seconds=5"),
                         process_index=0, attempt=0)
    t0 = time.monotonic()
    monkey.on_step(3)
    assert time.monotonic() - t0 < 1.0


# -- crash bundles -----------------------------------------------------------

def _mk_run_dir(tmp_path, monkeypatch):
    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir, exist_ok=True)
    monkeypatch.setenv("AUTODIST_TELEMETRY_DIR", run_dir)
    ev.configure(run_dir)
    return run_dir


def test_bundle_roundtrip(tmp_path, monkeypatch):
    run_dir = _mk_run_dir(tmp_path, monkeypatch)
    ev.emit_event("supervisor/attempt_start", attempt=0)
    fr.set_fingerprint("fp1")
    fr.record_cursor("A", step=6, leg_kind="all_reduce")

    legs = {"legs": CHAIN, "axes": {"data": 2}}
    verdicts = {
        "proc0": {"state": "wedged", "step": 6, "age": 1.0,
                  "cursor": {"leg": "C", "kind": "leg", "age_s": 40.0,
                             "fingerprint": "fp1"}},
        "proc1": {"state": "wedged", "step": 6, "age": 1.2,
                  "cursor": {"leg": "A", "kind": "leg", "age_s": 41.0,
                             "fingerprint": "fp1"}},
    }
    bundle = fr.dump_bundle(run_dir, reason="drill", ir=legs,
                            verdicts=verdicts)
    assert bundle is not None and os.path.isdir(bundle)
    b = fr.read_bundle(bundle)
    assert b["manifest"]["reason"] == "drill"
    assert b["manifest"]["fingerprint"] == "fp1"
    assert b["verdicts"]["proc1"]["cursor"]["leg"] == "A"
    assert b["diagnosis"]["culprits"] == ["proc1"]
    assert b["diagnosis"]["frontier_leg"] == "A"
    assert b["cursors"], "own cursor ring must be in the bundle"
    assert b["stacks"], "faulthandler stacks must be in the bundle"
    # events tail + schedule IR landed
    assert os.path.isfile(os.path.join(bundle, "events_tail.jsonl"))
    assert os.path.isfile(os.path.join(bundle, "schedule_ir.json"))
    # the hang diagnosis was journaled
    hang_events = [e for e in ev.load_run_events(run_dir)
                   if e["kind"] == fr.EVENT_HANG]
    assert len(hang_events) == 1
    assert hang_events[0]["culprits"] == ["proc1"]
    # find_bundles discovers it
    assert fr.find_bundles(run_dir) == [bundle]

    report = fr.render_hang_report(bundle)
    assert "culprit: proc1" in report
    assert "frontier leg: A" in report
    assert "in leg A" in report


def test_bundle_uses_published_ir(tmp_path, monkeypatch):
    run_dir = _mk_run_dir(tmp_path, monkeypatch)

    class _FakeIR:
        def fingerprint(self):
            return "fpX"

        def to_json(self):
            return json.dumps({"legs": CHAIN, "version": 1})

    assert fr.publish_ir(_FakeIR(), run_dir)
    assert fr.load_published_ir(run_dir)["legs"][0]["id"] == "A"
    verdicts = {"p0": {"state": "wedged",
                       "cursor": {"leg": "B", "kind": "leg"}},
                "p1": {"state": "wedged",
                       "cursor": {"leg": "C", "kind": "leg"}}}
    bundle = fr.dump_bundle(run_dir, reason="x", verdicts=verdicts)
    b = fr.read_bundle(bundle)
    assert b["diagnosis"]["culprits"] == ["p0"]
    assert b["diagnosis"]["frontier_leg"] == "B"


def test_hang_report_cli(tmp_path, monkeypatch):
    run_dir = _mk_run_dir(tmp_path, monkeypatch)
    verdicts = {"p0": {"state": "wedged",
                       "cursor": {"leg": "A", "kind": "leg"}},
                "p1": {"state": "wedged",
                       "cursor": {"leg": "C", "kind": "leg"}}}
    bundle = fr.dump_bundle(run_dir, reason="cli drill",
                            ir={"legs": CHAIN}, verdicts=verdicts)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "autodist_tpu.telemetry",
         "--hang-report", bundle],
        stdout=subprocess.PIPE, env=env, timeout=120)
    assert out.returncode == 0
    text = out.stdout.decode()
    assert "culprit: p0" in text and "cli drill" in text
    # a run dir works too (newest bundle picked), and the default
    # report grows a hang section
    out = subprocess.run(
        [sys.executable, "-m", "autodist_tpu.telemetry",
         "--hang-report", run_dir],
        stdout=subprocess.PIPE, env=env, timeout=120)
    assert out.returncode == 0 and "culprit: p0" in out.stdout.decode()
    out = subprocess.run(
        [sys.executable, "-m", "autodist_tpu.telemetry", run_dir],
        stdout=subprocess.PIPE, env=env, timeout=120)
    assert out.returncode == 0
    assert "crash bundle(s)" in out.stdout.decode()
    assert "--hang-report" in out.stdout.decode()


def test_hang_report_cli_no_bundle(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "autodist_tpu.telemetry",
         "--hang-report", str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
        timeout=120)
    assert out.returncode == 2


# -- supervisor wiring -------------------------------------------------------

def test_supervisor_attaches_bundle_on_failure(tmp_path, monkeypatch):
    run_dir = _mk_run_dir(tmp_path, monkeypatch)
    from autodist_tpu.resilience import Backoff, Supervisor, SupervisorPolicy

    policy = SupervisorPolicy(
        max_restarts=0,
        backoff=Backoff(max_tries=2, base=0.01, cap=0.02, seed=0))
    sup = Supervisor(policy, workdir=str(tmp_path / "sup"))

    def launch(att):
        return subprocess.Popen([sys.executable, "-c", "raise SystemExit(3)"],
                                start_new_session=True)

    report = sup.run(launch)
    assert not report.ok
    assert report.failures
    bundle = report.failures[0].bundle
    assert bundle is not None and os.path.isdir(bundle)
    assert bundle.startswith(run_dir)   # telemetry dir wins over workdir
    assert os.path.isfile(os.path.join(bundle, "MANIFEST.json"))
    fails = [e for e in ev.load_run_events(run_dir)
             if e["kind"] == "supervisor/attempt_failure"]
    assert fails and fails[0].get("bundle") == bundle


def test_install_fatal_handlers(tmp_path, monkeypatch):
    """Arming writes the faulthandler log target and an excepthook that
    dumps a bundle — exercised in-process by invoking the hook.

    Arming is once a PROCESS, and a worker runs other files' tests in the
    same process: where one of them armed the handlers for its own run
    directory first (a supervised session does), this test's call was a
    no-op and its bundle went there.  What was armed before is set aside
    for the test and comes back after it, hook and all."""
    monkeypatch.setattr(fr, "_fatal_armed", None)
    monkeypatch.setattr(sys, "excepthook", sys.excepthook)
    run_dir = str(tmp_path / "fatal")
    assert fr.install_fatal_handlers(run_dir)
    assert fr.install_fatal_handlers(run_dir)   # idempotent
    try:
        raise RuntimeError("boom")
    except RuntimeError:
        info = sys.exc_info()
    sys.excepthook(*info)
    bundles = fr.find_bundles(run_dir)
    assert bundles, "excepthook must dump a crash bundle"
    man = fr.read_bundle(bundles[-1])["manifest"]
    assert "RuntimeError" in man["reason"]


# -- live 2-process wedge drill (slow) ---------------------------------------

@pytest.mark.slow
def test_live_hang_drill(tmp_path):
    """The acceptance drill: chaos ``hang@step`` wedges the worker
    inside the step → the monitor's WEDGED verdict localizes to the
    planted leg and culprit process → a crash bundle is written and
    renders via --hang-report → the supervisor relaunch resumes from
    the peer tier bit-exact vs the uninterrupted oracle."""
    script = os.path.join(REPO, "tests", "integration", "hang_drill.py")

    def base_env(tag):
        env = dict(os.environ)
        for k in ("AUTODIST_WORKER", "AUTODIST_STRATEGY_ID",
                  "AUTODIST_CHAOS", "AUTODIST_SUPERVISE",
                  "AUTODIST_FAILURE_POLICY", "AUTODIST_SUPERVISOR_DIR",
                  "AUTODIST_ATTEMPT", "AUTODIST_TELEMETRY_DIR",
                  "AUTODIST_FLIGHTREC"):
            env.pop(k, None)
        env.update({
            "AUTODIST_REPO_ROOT": REPO,
            "AUTODIST_RESULT_FILE": str(tmp_path / f"result_{tag}.json"),
            "AUTODIST_TEST_PEER": str(tmp_path / f"peer_{tag}"),
            "AUTODIST_TPU_WORKDIR": str(tmp_path / f"workdir_{tag}"),
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        })
        return env

    import socket

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    # ORACLE: chaos off, single attempt.
    env = base_env("oracle")
    env["AUTODIST_COORDINATOR_ADDRESS"] = f"127.0.0.1:{free_port()}"
    proc = subprocess.run([sys.executable, "-u", script], env=env,
                          timeout=300, stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT)
    assert proc.returncode == 0, proc.stdout.decode()[-4000:]
    with open(env["AUTODIST_RESULT_FILE"], encoding="utf-8") as f:
        oracle = json.load(f)

    # DRILL: worker (proc 1) hangs inside step 6 of attempt 0.  The
    # drill script resolves the PLANT placeholder to a real leg id of
    # its schedule IR and records it in planted.json.
    env = base_env("drill")
    run_dir = str(tmp_path / "telemetry")
    env.update({
        "AUTODIST_SUPERVISE": "1",
        "AUTODIST_CHAOS": "hang@step=6,proc=1,attempt=0,leg=PLANT",
        "AUTODIST_TELEMETRY_DIR": run_dir,
        "AUTODIST_TEST_PLANTED": str(tmp_path / "planted.json"),
        "AUTODIST_SUPERVISOR_REPORT": str(tmp_path / "report.json"),
    })
    proc = subprocess.run([sys.executable, "-u", script], env=env,
                          timeout=600, stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT)
    out = proc.stdout.decode()
    assert proc.returncode == 0, out[-6000:]
    with open(env["AUTODIST_SUPERVISOR_REPORT"], encoding="utf-8") as f:
        report = json.load(f)
    assert report["ok"]
    assert report["attempts"] == 2
    fail = report["failures"][0]
    # the WEDGED verdict named the culprit process and the planted leg
    assert fail["kind"] == "heartbeat"
    assert "proc1" in (fail["culprit"] or "")
    assert "wedged" in fail["detail"]
    with open(env["AUTODIST_TEST_PLANTED"], encoding="utf-8") as f:
        planted = json.load(f)
    assert planted["leg"] in fail["detail"]
    # the bundle exists, renders, and localizes to the planted leg
    bundle = fail["bundle"]
    assert bundle and os.path.isdir(bundle)
    b = fr.read_bundle(bundle)
    diag = b.get("diagnosis") or {}
    assert diag.get("frontier_leg") == planted["leg"]
    assert diag.get("culprits") == ["proc1"]
    report_text = fr.render_hang_report(bundle)
    assert planted["leg"] in report_text
    assert "culprit: proc1" in report_text
    # recovery is bit-exact vs the uninterrupted oracle
    with open(env["AUTODIST_RESULT_FILE"], encoding="utf-8") as f:
        chief = json.load(f)
    assert chief["attempt"] == 1
    assert chief["final_step"] == oracle["final_step"]
    np.testing.assert_array_equal(chief["final_w"], oracle["final_w"])
    assert chief["final_b"] == oracle["final_b"]
