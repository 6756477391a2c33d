"""The readers of the program's own spans: exact-overlap idle attribution
on a hand-made trace, the set-up readers on a hand-made ring, the serving
readers rehearsed through the test-size serving cell, and every one of
them silent against a program that has no such spans."""
import importlib
import time
import types

import jax
import pytest

from benchmark import harness, program_spans, xplane

IDLE = ("train_idle_place_batch_pct", "train_idle_enqueue_pct",
        "train_idle_fetch_pct", "train_idle_unattributed_pct")
SETUP = ("setup_session_build_s", "setup_first_step_s",
         "setup_trace_lower_s")
SERVING = ("decode_host_share_pct", "driver_lock_wait_ms_p95")

# Chip 0 busy [0,10), [20,30), [40,50) us: two idle gaps of 10 us in a
# window of 50.  The host: autodist/session/run [8,36) with fetch [9,14),
# place_batch [14,16) and enqueue [16,22) nested in it, and the
# benchmark's own bench/sess.run over the same [8,36).
#   gap [10,20): fetch 4, place_batch 2, enqueue 4
#   gap [30,40): 6 under session/run alone, 4 under no autodist/ span
HAND = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 10000000 }
    events { metadata_id: 1 offset_ps: 40000000 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.7 = f32[8,8]{1,0} fusion(%p), kind=kLoop" } }
}
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 8000000 duration_ps: 28000000 }
    events { metadata_id: 2 offset_ps: 8000000 duration_ps: 28000000
             stats { metadata_id: 1 int64_value: 7 } }
    events { metadata_id: 3 offset_ps: 9000000 duration_ps: 5000000 }
    events { metadata_id: 4 offset_ps: 14000000 duration_ps: 2000000 }
    events { metadata_id: 5 offset_ps: 16000000 duration_ps: 6000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench/sess.run" } }
  event_metadata { key: 2 value { id: 2 name: "autodist/session/run" } }
  event_metadata { key: 3 value { id: 3 name: "autodist/session/fetch" } }
  event_metadata { key: 4 value { id: 4 name: "autodist/session/place_batch" } }
  event_metadata { key: 5 value { id: 5 name: "autodist/session/enqueue" } }
  stat_metadata { key: 1 value { id: 1 name: "step" } }
}
'''


class FakeRun:
    """What a reader takes from a ``harness.Run``."""

    def __init__(self, trace_path=None, window_start=None, records=()):
        self.trace_reduction = xplane.reduce(trace_path, 1) \
            if trace_path else None
        self.tracer = types.SimpleNamespace(xplane_path=lambda: trace_path)
        self.counters = {}
        self.window_start = window_start
        self.spans = types.SimpleNamespace(records=list(records))


def reader(name):
    return harness._load_reader(harness.Cell("train.gpt2-medium.1chip"),
                                name)


@pytest.fixture
def ring():
    from autodist_tpu.telemetry import profiler

    profiler.reset_spans_for_testing()
    yield profiler.get_span_writer()
    profiler.reset_spans_for_testing()


@pytest.fixture
def hand_trace(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(HAND))
    return str(path)


def test_trace_spans_take_the_prefix_off_and_keep_the_ids(hand_trace):
    spans = program_spans.trace_spans(hand_trace)
    assert sorted(s[0] for s in spans) == [
        "session/enqueue", "session/fetch", "session/place_batch",
        "session/run"]
    run = next(s for s in spans if s[0] == "session/run")
    assert run[1:3] == (8000.0, 36000.0) and run[3] == {"step": 7}


def test_innermost_segments_follow_the_nest():
    spans = [("a", 0, 100), ("b", 10, 40), ("c", 20, 30), ("d", 120, 130)]
    assert program_spans.innermost_segments(spans) == [
        (0, 10, "a"), (10, 20, "b"), (20, 30, "c"), (30, 40, "b"),
        (40, 100, "a"), (120, 130, "d")]


@pytest.mark.parametrize("name,want", zip(IDLE, (4.0, 8.0, 8.0, 20.0)))
def test_idle_split_by_exact_overlap(hand_trace, name, want):
    """Each idle interval is cut at span edges and each piece goes to
    the innermost span: place_batch 2 us of 50, enqueue 4, fetch 4, and
    the rest (under ``session/run`` alone, and under no span) 10."""
    assert reader(name)(FakeRun(hand_trace)) == pytest.approx(want)


def test_idle_metrics_add_up_to_chip_0s_idle_share(hand_trace):
    run = FakeRun(hand_trace)
    assert run.trace_reduction.idle_share == pytest.approx(0.4)
    assert sum(reader(n)(run) for n in IDLE) == pytest.approx(
        100.0 * run.trace_reduction.idle_share)
    # winner-takes-all (the breakdown's way) gives both gaps to one span
    assert run.trace_reduction.idle_gaps() == [
        ["bench/sess.run", pytest.approx(20e-6)]]


def test_setup_readers_on_a_hand_made_ring(ring):
    """``setup/*`` spans are summed; the first step is ``session/run``
    with step 0; tracing and lowering are the UNION of their records
    before the window, those inside the benchmark's reference and
    lowered-types records left out."""
    for name, start, end in (("setup/build_strategy", 10.0, 10.5),
                             ("setup/transform", 10.5, 11.0),
                             ("setup/place_params", 11.0, 12.5)):
        ring.record(name, start=start, end=end)
    ring.record("session/run", start=13.0, end=20.0, step=0)
    ring.record("session/run", start=20.0, end=20.1, step=1)
    for name, start, end in (
            ("compile/trace", 2.0, 4.0),       # the reference's: left out
            ("compile/trace", 13.5, 14.0),     # nested in the next one
            ("compile/trace", 13.0, 16.0),
            ("compile/lower", 16.0, 17.5),
            ("compile/backend", 17.5, 19.5),   # XLA's: compile_s has it
            ("compile/lower", 21.0, 22.0),     # lowered for its types
            ("compile/trace", 31.0, 32.0)):    # after the window opened
        ring.record(name, start=start, end=end, fun_name="f")
    run = FakeRun(window_start=30.0,
                  records=[("bench/reference", 1.0, 9.0),
                           ("bench/sess.run", 13.0, 20.0),
                           ("bench/lowered_types", 20.5, 22.5)])
    assert reader("setup_session_build_s")(run) == pytest.approx(2.5)
    assert reader("setup_first_step_s")(run) == pytest.approx(7.0)
    assert reader("setup_trace_lower_s")(run) == pytest.approx(3.0 + 1.5)


@pytest.mark.parametrize("name", IDLE + SETUP + SERVING)
def test_a_program_without_the_spans_reads_as_nothing(ring, name,
                                                      tmp_path):
    """The parent commit has no ``autodist/`` annotation and no such
    record in its ring (its records carry ``start_unix`` alone): every
    reader returns None and raises nothing."""
    from jax.profiler import ProfileData

    path = tmp_path / "parent.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        HAND.replace("autodist/", "other/")))
    ring._memory.append({"name": "request", "start_unix": 1.0,
                         "dur_s": 1.0})
    assert reader(name)(FakeRun(str(path), window_start=0.0)) is None
    assert reader(name)(FakeRun(window_start=0.0)) is None


def drive(cell, seconds=1.0):
    """``run.py`` after its look for a chip: the run, and what the entry
    kept alive (the training session)."""
    run = harness.Run(cell, 2**31 + 9, seconds, False,
                      jax.devices()[:cell.chips], time.perf_counter())
    return run, importlib.import_module(
        "benchmark.entries." + cell.workload["entry"]).run(run)


def test_setup_readers_through_the_training_cell(tiny_cell, ring):
    """The normal way in at test size: the three set-up readers find
    their spans, and what they read lies inside ``setup_s``."""
    run, sess = drive(tiny_cell("train.gpt2-tiny.cpu"))
    build, first, lower = (reader(n)(run) for n in SETUP)
    assert build > 0 and first > 0 and 0 < lower < first + build
    assert build + first < run.e2e["setup_s"]
    assert all({"dispatch", "place_batch", "enqueue", "fetch"} <= set(
        r.phases) for r in sess.telemetry.records)


def test_serving_readers_through_the_serving_cell(tiny_cell, ring):
    """``decode_host_share_pct`` and ``driver_lock_wait_ms_p95`` have no
    cell yet; rehearsed through the test-size serving cell they read the
    window's ``engine/step``, ``engine/host_sync`` and
    ``server/lock_wait`` records."""
    run, _ = drive(tiny_cell("serve.gpt2-tiny.cpu"), seconds=2.0)
    assert run.correct
    share = reader("decode_host_share_pct")(run)
    assert 0.0 < share < 100.0
    wait = reader("driver_lock_wait_ms_p95")(run)
    assert wait is not None and wait >= 0.0
    in_window = program_spans.ring_spans("engine/step",
                                         since=run.window_start)
    assert in_window and len(in_window) < len(
        program_spans.ring_spans("engine/step"))
