"""Fast-recovery checkpoint tiers (docs/resilience.md): RAM snapshot
ring + digest rule, peer mirroring, restore routing, deadline-aware
preemption, DRAINING heartbeats, goodput math, and the new chaos
grammar.  The multiprocess kill → survivor-peer-restore drill lives in
``tests/integration/recovery_drill.py`` (driven by the slow-tagged test
at the bottom)."""
import json
import os
import signal
import socket
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

pytestmark = pytest.mark.resilience


@pytest.fixture(autouse=True)
def _testing_env(monkeypatch):
    from autodist_tpu.autodist import _reset_default_autodist_for_testing
    from autodist_tpu.checkpoint import saver as saver_mod

    monkeypatch.setenv("AUTODIST_IS_TESTING", "True")
    monkeypatch.delenv("AUTODIST_PREEMPT_GRACE_S", raising=False)
    monkeypatch.delenv("AUTODIST_SNAPSHOT_EVERY", raising=False)
    _reset_default_autodist_for_testing()
    yield
    saver_mod.clear_save_hooks()


def _linear_session(lr=1e-2):
    import jax.numpy as jnp
    import optax

    from autodist_tpu.autodist import (
        AutoDist, _reset_default_autodist_for_testing)
    from autodist_tpu.strategy import AllReduce

    _reset_default_autodist_for_testing()
    rng = np.random.RandomState(0)
    x = rng.randn(16, 8).astype(np.float32)
    w = rng.randn(8, 4).astype(np.float32)
    params = {"linear": {"w": jnp.zeros((8, 4), jnp.float32),
                         "b": jnp.zeros((4,), jnp.float32)}}

    def loss_fn(p, b):
        pred = b["x"] @ p["linear"]["w"] + p["linear"]["b"]
        return jnp.mean((pred - b["y"]) ** 2)

    ad = AutoDist(strategy_builder=AllReduce())
    with ad.scope():
        ad.capture(params=params, optimizer=optax.adam(lr),
                   loss_fn=loss_fn)
    return ad.create_distributed_session(), \
        {"x": x, "y": (x @ w).astype(np.float32)}


# ---------------------------------------------------------------------------
# snapshot ring + digest rule
# ---------------------------------------------------------------------------

def test_snapshot_ring_keeps_last_k_and_drops_tampered():
    from autodist_tpu.checkpoint.tiers import RamSnapshot, SnapshotRing
    from autodist_tpu.checkpoint.saver import _tree_digest

    def snap(step, value):
        leaves = {"params": [np.full((4,), value, np.float32)],
                  "opt_state": [np.zeros((2,), np.float32)]}
        return RamSnapshot(step=step, leaves=leaves,
                           digest=_tree_digest([leaves[k]
                                                for k in sorted(leaves)]))

    ring = SnapshotRing(keep=2)
    for s in (2, 4, 6):
        ring.add(snap(s, float(s)))
    assert ring.steps() == [4, 6]          # keep=2 evicted step 2
    assert ring.latest().step == 6
    assert ring.nbytes > 0

    # tamper with the newest: the digest re-check drops it and latest()
    # falls back to the previous snapshot (the Saver.latest_step analog)
    ring.get(6).leaves["params"][0][0] = 999.0
    assert ring.latest().step == 4
    assert ring.steps() == [4]

    with pytest.raises(ValueError):
        SnapshotRing(keep=0)


def test_snapshot_serialization_roundtrip_and_corruption():
    from autodist_tpu.checkpoint.tiers import (
        RamSnapshot, SnapshotError, snapshot_from_bytes, snapshot_to_bytes)
    from autodist_tpu.checkpoint.saver import _tree_digest

    leaves = {"params": [np.arange(12, dtype=np.float32).reshape(3, 4),
                         np.ones((2,), np.int32)],
              "opt_state": [np.zeros((5,), np.float32)]}
    snap = RamSnapshot(step=7, leaves=leaves,
                       digest=_tree_digest([leaves[k]
                                            for k in sorted(leaves)]),
                       meta={"mesh_axes": {"data": 1},
                             "data_state": {"epoch": 1, "offset": 3}})
    blob = snapshot_to_bytes(snap)
    back = snapshot_from_bytes(blob)
    assert back.step == 7 and back.verify()
    assert back.meta["data_state"] == {"epoch": 1, "offset": 3}
    for item in leaves:
        for a, b in zip(leaves[item], back.leaves[item]):
            np.testing.assert_array_equal(a, b)

    with pytest.raises(SnapshotError):
        snapshot_from_bytes(blob[: len(blob) // 2])   # truncated wire blob


def test_peer_mirror_push_fetch_retention_and_digest(tmp_path):
    from autodist_tpu.checkpoint.tiers import (
        PeerMirror, RamSnapshot, buddy_of, snapshot_to_bytes)
    from autodist_tpu.checkpoint.saver import _tree_digest

    assert buddy_of(["a", "b", "c"], "a") == "b"
    assert buddy_of(["a", "b", "c"], "c") == "a"
    assert buddy_of(["a"], "a") is None
    assert buddy_of(["a", "b"], "zz") is None

    mirror = PeerMirror(str(tmp_path / "peer"), keep=2)

    def snap(step):
        leaves = {"params": [np.full((3,), float(step), np.float32)],
                  "opt_state": [np.zeros((2,), np.float32)]}
        return RamSnapshot(step=step, leaves=leaves,
                           digest=_tree_digest([leaves[k]
                                                for k in sorted(leaves)]))

    for s in (2, 4, 6):
        mirror.push(snap(s), owner="proc0")
    assert mirror.steps("proc0") == [4, 6]     # retention on the mirror
    got = mirror.fetch("proc0")
    assert got.step == 6 and got.verify()

    # corrupt the newest mirrored blob: fetch skips to the previous one
    path = os.path.join(str(tmp_path / "peer"), "proc0",
                        "snap_step_6.npz")
    with open(path, "wb") as f:
        f.write(b"garbage")
    assert mirror.fetch("proc0").step == 4
    # fetch_any finds the other owner's newest
    mirror.push(snap(8), owner="proc1")
    assert mirror.fetch_any().step == 8
    mirror.clear()
    assert mirror.owners() == []
    # no litter: a finished drill leaves no file in the peer store
    assert not [f for _, _, files in os.walk(str(tmp_path / "peer"))
                for f in files]


# ---------------------------------------------------------------------------
# restore routing
# ---------------------------------------------------------------------------

def test_route_restore_newest_wins_and_falls_through(tmp_path):
    from autodist_tpu.checkpoint import Saver
    from autodist_tpu.checkpoint.tiers import (
        CheckpointTiers, route_restore)

    sess, batch = _linear_session()
    ckpt = str(tmp_path / "ck")
    peer = str(tmp_path / "peer")
    tiers = CheckpointTiers(sess, snapshot_every=1, keep=3, peer_dir=peer)
    saver = Saver(sess)

    sess.run(batch)
    saver.save(ckpt, step=1)           # persistent @1
    sess.run(batch)
    tiers.snapshot(step=2)             # ram+peer @2 (newer)
    w2 = np.asarray(sess.params["linear"]["w"]).copy()
    sess.run(batch)                    # step 3 never snapshotted

    # newest usable state is the RAM snapshot @2
    fresh, _ = _linear_session()
    t_fresh = CheckpointTiers(fresh, snapshot_every=1, peer_dir=peer)
    step, tier, meta = route_restore(fresh, ckpt, tiers=t_fresh)
    assert (step, tier) == (2, "peer")   # fresh process: ring empty
    np.testing.assert_array_equal(
        np.asarray(fresh.params["linear"]["w"]), w2)

    # the ORIGINAL process still holds the ring: ram wins the tie
    step, tier, _ = route_restore(sess, ckpt, tiers=tiers)
    assert (step, tier) == (2, "ram")

    # corrupt every peer blob: routing falls through to persistent @1
    import shutil
    shutil.rmtree(peer)
    fresh2, _ = _linear_session()
    t2 = CheckpointTiers(fresh2, snapshot_every=1, peer_dir=peer)
    step, tier, _ = route_restore(fresh2, ckpt, tiers=t2)
    assert (step, tier) == (1, "persistent")

    # nothing anywhere -> None
    fresh3, _ = _linear_session()
    assert route_restore(fresh3, str(tmp_path / "empty")) is None


def test_fit_snapshot_every_and_peer_resume_parity(tmp_path):
    """fit(snapshot_every=K) populates the tiers mid-run; a fresh
    process resumes from the PEER tier alone (no persistent dir) and —
    because it replays the lost tail deterministically — lands on
    exactly the oracle's parameters, having lost at most K steps."""
    from autodist_tpu.checkpoint.tiers import CheckpointTiers
    from autodist_tpu.runtime.data_loader import DataLoader

    peer = str(tmp_path / "peer")

    def loader():
        rng = np.random.RandomState(1)
        return DataLoader({"x": rng.randn(32, 8).astype(np.float32),
                           "y": rng.randn(32, 4).astype(np.float32)},
                          batch_size=8, shuffle=True, seed=7)

    # oracle: 3 epochs uninterrupted
    oracle, _ = _linear_session()
    oracle.fit(loader(), epochs=3)
    w_oracle = np.asarray(oracle.params["linear"]["w"]).copy()

    # attempt A: runs 2 of 3 epochs with the RAM tier, then "dies"
    a, _ = _linear_session()
    hist = a.fit(loader(), epochs=2, snapshot_every=2, snapshot_dir=peer)
    assert hist.steps_run == 8
    assert os.path.isdir(peer)

    # attempt B: fresh process, peer tier only (ring empty, no
    # persistent checkpoints anywhere) — must resume ≤2 steps back and
    # complete to the oracle's trajectory exactly
    b, _ = _linear_session()
    tiers_b = CheckpointTiers(b, snapshot_every=2, peer_dir=peer)
    hist_b = b.fit(loader(), epochs=3, tiers=tiers_b, resume=True)
    assert hist_b.resume_tier == "peer"
    assert b.step_count == 12
    # at most snapshot_every steps were replayed beyond the remaining
    # epoch: 12 total - resumed step (8) = 4 = one epoch, no extra loss
    assert hist_b.steps_run <= 4 + 2
    np.testing.assert_allclose(np.asarray(b.params["linear"]["w"]),
                               w_oracle, rtol=1e-6, atol=1e-7)
    # per-attempt goodput accounting rode along
    assert hist_b.goodput and hist_b.goodput["steps"] == hist_b.steps_run


# ---------------------------------------------------------------------------
# deadline-aware preemption
# ---------------------------------------------------------------------------

def _preempt_fit(sess, batch, tmp_path, grace=None, stall=0.0,
                 snapshot_every=2):
    """Run fit with a chaos preemption at step 3 under the given grace/
    storage conditions; returns (history, ckpt_dir, peer_dir)."""
    from autodist_tpu.checkpoint import saver as saver_mod
    from autodist_tpu.resilience import ChaosCallback, ChaosMonkey
    from autodist_tpu.resilience.chaos import parse_chaos

    ckpt = str(tmp_path / "ck")
    peer = str(tmp_path / "peer")
    spec = "preempt@step=3,signal=SIGUSR1" + \
        (f",grace={grace}" if grace is not None else "")
    if stall:
        saver_mod.set_storage_stall(stall)
    monkey = ChaosMonkey(parse_chaos(spec))
    hist = sess.fit({"x": batch["x"], "y": batch["y"]},
                    epochs=2, steps_per_epoch=4,
                    checkpoint_dir=ckpt, checkpoint_every=1,
                    snapshot_every=snapshot_every, snapshot_dir=peer,
                    callbacks=[ChaosCallback(monkey)],
                    preemption_signals=("SIGUSR1",))
    return hist, ckpt, peer


def test_preempt_without_grace_takes_persistent_tier(tmp_path):
    from autodist_tpu.checkpoint import Saver

    sess, batch = _linear_session()
    hist, ckpt, _ = _preempt_fit(sess, batch, tmp_path, grace=None)
    assert hist.preempted and hist.preempt_tier == "persistent"
    assert Saver.latest_step(ckpt) == 3     # saved AT the preempted step


def test_preempt_grace_routes_to_peer_tier(tmp_path, monkeypatch):
    """A tight grace deadline with slow storage: the persistent save
    cannot finish, so the emergency snapshot goes to the peer tier and
    the persistent dir gains NO step at the preempted step."""
    from autodist_tpu.checkpoint import Saver
    from autodist_tpu.checkpoint.tiers import PeerMirror
    from autodist_tpu.telemetry import get_journal

    sess, batch = _linear_session()
    # tiny grace + a measured slow save (the storage stall inflates the
    # first epoch save's measured duration past the deadline)
    hist, ckpt, peer = _preempt_fit(sess, batch, tmp_path,
                                    grace=0.05, stall=0.2)
    assert hist.preempted and hist.preempt_tier == "peer"
    # the peer tier holds the preempted step; persistent stayed behind
    assert PeerMirror(peer).fetch_any().step == 3
    assert (Saver.latest_step(ckpt) or 0) < 3
    kinds = [e.get("kind") for e in get_journal().events]
    assert "checkpoint/preempt_decision" in kinds

    # and the resumed fit routes through the PEER tier to step 3
    sess2, _ = _linear_session()
    hist2 = sess2.fit({"x": batch["x"], "y": batch["y"]},
                      epochs=2, steps_per_epoch=4, checkpoint_dir=ckpt,
                      snapshot_every=2, snapshot_dir=peer)
    assert hist2.resume_tier == "peer"
    # dict data has no loader state: the partial epoch re-runs (steps
    # 4..7), then epoch 1 — Keras initial_epoch semantics
    assert not hist2.preempted and sess2.step_count == 11


# ---------------------------------------------------------------------------
# supervisor: preemption exit code is budget-free
# ---------------------------------------------------------------------------

def _proc(code: int) -> subprocess.Popen:
    return subprocess.Popen(
        [sys.executable, "-c", f"raise SystemExit({code})"],
        start_new_session=True)


def test_supervisor_preempt_relaunch_keeps_restart_budget(tmp_path):
    """Two preemption exits (75) then success, under max_restarts=0:
    a real failure would give up immediately, preemptions must not."""
    from autodist_tpu.resilience import (
        Backoff, PREEMPTED_EXIT_CODE, Supervisor, SupervisorPolicy)

    assert PREEMPTED_EXIT_CODE == 75
    codes = [75, 75, 0]

    def launch(att):
        return _proc(codes[att.index])

    policy = SupervisorPolicy(
        max_restarts=0,
        backoff=Backoff(max_tries=8, base=0.01, cap=0.02, jitter=0,
                        seed=0),
        poll_interval=0.02)
    sup = Supervisor(policy, hosts=["a"], workdir=str(tmp_path))
    report = sup.run(launch)
    assert report.ok and report.attempts == 3
    assert report.preemptions == 2
    assert all(f.kind == "preempt" for f in report.failures)

    # the backstop still bounds a pathological preemption loop
    policy2 = SupervisorPolicy(
        max_restarts=0, max_preemptions=2,
        backoff=Backoff(max_tries=8, base=0.01, cap=0.02, jitter=0,
                        seed=0),
        poll_interval=0.02)
    sup2 = Supervisor(policy2, hosts=["a"],
                      workdir=str(tmp_path / "w2"))
    report2 = sup2.run(lambda att: _proc(75))
    assert not report2.ok and "preemption backstop" in report2.gave_up


# ---------------------------------------------------------------------------
# heartbeats: DRAINING + phase-tagged checkpoint stalls
# ---------------------------------------------------------------------------

def test_heartbeat_draining_not_wedged(tmp_path):
    from autodist_tpu.resilience.heartbeat import (
        ALIVE, DRAINING, HeartbeatMonitor, HeartbeatWriter, WEDGED)

    d = str(tmp_path)
    w = HeartbeatWriter(d, "w1", interval=60)
    mon = HeartbeatMonitor(d, timeout=30.0, step_timeout=0.05)
    w.beat(step=5)
    assert mon.check("w1").state == ALIVE
    time.sleep(0.1)
    w.set_phase("draining")                 # grace window opens
    h = mon.check("w1")
    assert h.state == DRAINING and "drain" in h.detail
    assert "w1" not in mon.failures()       # draining is NOT a failure
    w.set_phase(None)
    time.sleep(0.1)
    w.beat(step=5)                          # stall persists, no phase
    assert mon.check("w1").state == WEDGED


def test_heartbeat_checkpoint_phase_suppresses_step_stall(tmp_path):
    from autodist_tpu.resilience.heartbeat import (
        ALIVE, HeartbeatMonitor, HeartbeatWriter, heartbeat_phase,
        set_active_writer)

    d = str(tmp_path)
    w = HeartbeatWriter(d, "w1", interval=60)
    mon = HeartbeatMonitor(d, timeout=30.0, step_timeout=0.05)
    w.beat(step=9)
    mon.check("w1")
    time.sleep(0.1)
    set_active_writer(w)
    try:
        with heartbeat_phase("checkpoint/restore"):
            h = mon.check("w1")
            assert h.state == ALIVE and "phase-tagged" in h.detail
    finally:
        set_active_writer(None)
    # phase cleared, stall still there -> the wedge verdict returns
    w.beat(step=9)
    assert mon.check("w1").state == "wedged"


def test_saver_save_bumps_heartbeat_phase(tmp_path):
    """Saver.save on a registered writer leaves phase-tagged beacons —
    the satellite: long saves can't trip the step_timeout verdict."""
    from autodist_tpu.checkpoint import Saver
    from autodist_tpu.resilience.heartbeat import (
        HeartbeatWriter, set_active_writer)

    sess, batch = _linear_session()
    sess.run(batch)
    w = HeartbeatWriter(str(tmp_path / "hb"), "w0", interval=60)
    seen = []
    orig = w.beat

    def spy_beat(*a, **kw):
        seen.append(w._phase)
        return orig(*a, **kw)

    w.beat = spy_beat
    set_active_writer(w)
    try:
        Saver(sess).save(str(tmp_path / "ck"))
    finally:
        set_active_writer(None)
    assert "checkpoint/save" in seen


# ---------------------------------------------------------------------------
# chaos grammar: storage_stall, kill during=save
# ---------------------------------------------------------------------------

def test_chaos_storage_stall_blocks_saves(tmp_path):
    from autodist_tpu.checkpoint import Saver, saver as saver_mod
    from autodist_tpu.resilience import ChaosMonkey
    from autodist_tpu.resilience.chaos import parse_chaos

    sess, batch = _linear_session()
    sess.run(batch)
    monkey = ChaosMonkey(parse_chaos("storage_stall@step=1,seconds=0.15"),
                         process_index=0)
    monkey.on_step(1)
    t0 = time.perf_counter()
    Saver(sess).save(str(tmp_path / "ck"))
    assert time.perf_counter() - t0 >= 0.15
    saver_mod.set_storage_stall(0)


def test_chaos_kill_during_save_arms_pre_save_hook(tmp_path):
    from autodist_tpu.checkpoint import Saver
    from autodist_tpu.resilience import ChaosMonkey
    from autodist_tpu.resilience.chaos import parse_chaos

    sess, batch = _linear_session()
    sess.run(batch)
    monkey = ChaosMonkey(parse_chaos("kill@step=1,during=save,code=43"),
                         process_index=0)
    exits = []
    monkey._exit = exits.append          # the documented test seam
    monkey.on_step(1)
    assert exits == []                   # NOT dead at the step boundary
    Saver(sess).save(str(tmp_path / "ck"))
    assert exits == [43]                 # died INSIDE the save


def test_chaos_preempt_grace_stamps_env(monkeypatch):
    from autodist_tpu.resilience import ChaosMonkey
    from autodist_tpu.resilience.chaos import parse_chaos

    monkeypatch.delenv("AUTODIST_PREEMPT_GRACE_S", raising=False)
    fired = []
    monkeypatch.setattr(os, "kill", lambda pid, sig: fired.append(sig))
    monkey = ChaosMonkey(parse_chaos("preempt@step=2,grace=3.5"),
                         process_index=0)
    monkey.on_step(2)
    assert fired == [signal.SIGTERM]
    assert os.environ["AUTODIST_PREEMPT_GRACE_S"] == "3.5"


# ---------------------------------------------------------------------------
# fit durability: the finally-wait satellite
# ---------------------------------------------------------------------------

def test_fit_exception_path_waits_for_async_save(tmp_path):
    """A callback crash racing an ASYNC save: the finally must make the
    in-flight save durable before fit unwinds, so the step dir commits
    instead of stranding half-written."""
    from autodist_tpu.checkpoint import Saver
    from autodist_tpu.fit import Callback

    sess, batch = _linear_session()
    ckpt = str(tmp_path / "ck")

    class Bomb(Callback):
        def on_epoch_begin(self, epoch):
            if epoch == 2:
                # the epoch-1 async save is still in flight right here
                raise RuntimeError("boom with a save in flight")

    with pytest.raises(RuntimeError, match="boom"):
        sess.fit({"x": batch["x"], "y": batch["y"]}, epochs=3,
                 steps_per_epoch=2, checkpoint_dir=ckpt,
                 checkpoint_every=1, async_checkpoints=True,
                 callbacks=[Bomb()])
    # both epoch saves are committed and verify cleanly
    assert Saver.latest_step(ckpt) == 4
    assert Saver.verify(os.path.join(ckpt, "step_4"), deep=True)


# ---------------------------------------------------------------------------
# goodput math + recovery-gap rule
# ---------------------------------------------------------------------------

def test_goodput_decomposition_pure_math():
    from autodist_tpu.telemetry import StepRecord
    from autodist_tpu.telemetry.goodput import (
        attempt_goodput, checkpoint_cadence, goodput_from_run)

    t0 = 1000.0
    records = [StepRecord(step=s, time_unix=t0 + s, step_time_s=0.1,
                          host="h0") for s in range(1, 9)]
    # steps 5..6 re-run after the restart (recorded twice)
    records += [StepRecord(step=s, time_unix=t0 + 20 + s, step_time_s=0.1,
                           host="h0") for s in (5, 6)]
    events = [
        {"time": t0, "kind": "supervisor/attempt_start", "attempt": 0},
        {"time": t0 + 4, "kind": "checkpoint/save", "step": 4,
         "duration_s": 0.5},
        {"time": t0 + 9, "kind": "checkpoint/save", "step": 8,
         "duration_s": 0.5},
        {"time": t0 + 10, "kind": "supervisor/attempt_failure"},
        {"time": t0 + 15, "kind": "supervisor/attempt_start",
         "attempt": 1},
        {"time": t0 + 30, "kind": "checkpoint/ram_snapshot", "step": 6,
         "duration_s": 0.05},
    ]
    gp = goodput_from_run(records, events)
    assert gp["steps"] == 8
    assert gp["useful_step_s"] == pytest.approx(0.8)
    assert gp["attempts"] == 2
    assert gp["losses"]["restart_s"] == pytest.approx(5.0)   # t+10 -> t+15
    assert gp["losses"]["checkpoint_stall_s"] == pytest.approx(1.05)
    assert gp["losses"]["rollback_s"] == pytest.approx(0.2)  # 2 re-run
    assert gp["wall_s"] == pytest.approx(30.0)
    assert gp["goodput_ratio"] == pytest.approx(0.8 / 30.0, abs=1e-4)

    cad = checkpoint_cadence(records, events)
    assert cad["checkpoint_interval_steps"] == 4
    assert cad["step_time_s"] == pytest.approx(0.1)

    ag = attempt_goodput(10.0, 8.0, ckpt_stall_s=1.0, steps=80)
    assert ag["goodput_ratio"] == pytest.approx(0.8)
    assert attempt_goodput(10.0, None)["goodput_ratio"] is None


def test_recovery_gap_reason_thresholds():
    from autodist_tpu.telemetry.goodput import recovery_gap_reason

    # 1000 steps x 0.5s = 500s exposure > 120s budget
    why = recovery_gap_reason(1000, 0.5)
    assert why is not None and "recovery exposure" in why
    # a RAM tier at 100 steps caps the exposure at 50s -> quiet
    assert recovery_gap_reason(1000, 0.5, snapshot_every=100) is None
    # a RAM tier that is still too coarse fires, naming the tier
    why = recovery_gap_reason(1000, 0.5, snapshot_every=500)
    assert why is not None and "RAM snapshots" in why
    assert recovery_gap_reason(10, 0.5) is None
    assert recovery_gap_reason(None, 0.5) is None
    assert recovery_gap_reason(1000, None) is None


@pytest.mark.analysis
def test_recovery_gap_lint_fires():
    """analysis pass `resilience`: WARN on an exposed cadence, quiet
    when a tier bounds it, inert without provenance."""
    import jax.numpy as jnp

    from autodist_tpu.analysis import analyze
    from autodist_tpu.graph_item import GraphItem
    from autodist_tpu.strategy import AllReduce
    from autodist_tpu.resource_spec import ResourceSpec

    params = {"w": jnp.zeros((64, 64), jnp.float32)}
    gi = GraphItem(params)
    spec = ResourceSpec(resource_info={"nodes": [
        {"address": "127.0.0.1", "chips": 8, "chief": True}]})
    strat = AllReduce().build(gi, spec)

    report = analyze(strat, gi, mesh={"data": 8},
                     resilience={"checkpoint_interval_steps": 2000,
                                 "step_time_s": 0.25})
    assert any(d.rule == "resilience/recovery-gap"
               for d in report.warnings)

    report = analyze(strat, gi, mesh={"data": 8},
                     resilience={"checkpoint_interval_steps": 2000,
                                 "step_time_s": 0.25,
                                 "snapshot_every": 50})
    assert not any(d.rule.startswith("resilience/")
                   for d in report.diagnostics)

    report = analyze(strat, gi, mesh={"data": 8})
    assert not any(d.rule.startswith("resilience/")
                   for d in report.diagnostics)

    report = analyze(strat, gi, mesh={"data": 8},
                     resilience={"step_time_s": 0.25})
    assert any(d.rule == "resilience/no-measurement"
               for d in report.diagnostics)


def test_fit_emits_goodput_event_and_gauge(tmp_path):
    from autodist_tpu.telemetry import get_journal
    from autodist_tpu.telemetry.registry import DEFAULT_REGISTRY

    sess, batch = _linear_session()
    hist = sess.fit({"x": batch["x"], "y": batch["y"]}, epochs=1,
                    steps_per_epoch=4,
                    checkpoint_dir=str(tmp_path / "ck"))
    assert hist.goodput is not None
    assert hist.goodput["steps"] == 4
    assert hist.goodput["checkpoint_stall_s"] > 0
    ev = [e for e in get_journal().events
          if e.get("kind") == "goodput/attempt"]
    assert ev and ev[-1]["steps"] == 4
    gauges = [m for m in DEFAULT_REGISTRY.metrics()
              if m.name == "autodist_goodput_ratio"]
    if hist.goodput["goodput_ratio"] is not None:
        assert gauges and 0 < gauges[0].value <= 1.0


# ---------------------------------------------------------------------------
# the live multiprocess drill (slow)
# ---------------------------------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DRILL = os.path.join(REPO, "tests", "integration", "recovery_drill.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_peer_tier_drill_survives_worker_kill(tmp_path):
    """SIGKILL-grade chaos kill of the worker mid-run; the relaunch
    resumes from the PEER tier (no persistent checkpoint exists at all)
    and ends bit-exact with the uninterrupted oracle."""
    def base_env(tag):
        env = dict(os.environ)
        for k in ("AUTODIST_WORKER", "AUTODIST_CHAOS", "AUTODIST_SUPERVISE",
                  "AUTODIST_FAILURE_POLICY", "AUTODIST_SUPERVISOR_DIR",
                  "AUTODIST_ATTEMPT", "AUTODIST_SNAPSHOT_EVERY",
                  "AUTODIST_SNAPSHOT_DIR"):
            env.pop(k, None)
        env.update({
            "AUTODIST_REPO_ROOT": REPO,
            "AUTODIST_RESULT_FILE": str(tmp_path / f"result_{tag}.json"),
            "AUTODIST_TEST_PEER": str(tmp_path / f"peer_{tag}"),
            "AUTODIST_TPU_WORKDIR": str(tmp_path / f"workdir_{tag}"),
            "AUTODIST_COORDINATOR_ADDRESS": f"127.0.0.1:{_free_port()}",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        })
        return env

    def run(env, timeout=300):
        proc = subprocess.run([sys.executable, "-u", DRILL], env=env,
                              timeout=timeout, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT)
        return proc.returncode, proc.stdout.decode()

    env = base_env("oracle")
    rc, out = run(env)
    assert rc == 0, f"oracle failed (rc={rc}):\n{out[-4000:]}"
    with open(env["AUTODIST_RESULT_FILE"], encoding="utf-8") as f:
        oracle = json.load(f)
    assert oracle["final_step"] == 16

    env = base_env("drill")
    env.update({
        "AUTODIST_SUPERVISE": "1",
        "AUTODIST_CHAOS": "kill@step=6,proc=1,attempt=0",
        "AUTODIST_SUPERVISOR_REPORT": str(tmp_path / "report.json"),
    })
    rc, out = run(env, timeout=480)
    assert rc == 0, f"drill failed (rc={rc}):\n{out[-6000:]}"
    with open(env["AUTODIST_SUPERVISOR_REPORT"], encoding="utf-8") as f:
        report = json.load(f)
    assert report["ok"] and report["attempts"] == 2

    with open(env["AUTODIST_RESULT_FILE"], encoding="utf-8") as f:
        chief = json.load(f)
    # attempt 1 resumed from the PEER tier without any persistent dir,
    # losing at most snapshot_every(=2) steps of the 6 attempt 0 ran
    assert chief["attempt"] == 1
    assert chief["resume_tier"] == "peer"
    assert chief["resumed_step"] >= 4
    assert chief["final_step"] == 16
    np.testing.assert_allclose(chief["final_w"], oracle["final_w"],
                               rtol=1e-7, atol=1e-8)
    np.testing.assert_allclose(chief["final_b"], oracle["final_b"],
                               rtol=1e-7, atol=1e-8)
