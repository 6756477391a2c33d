"""The trace reduction: interval arithmetic on a hand-made trace, and the
recorded cuts of the benchmark's own cells with the gpt2 cells' readers
on them (one chip: PR 23's traced run; four chips: seed 3100000303 of
PR 31, one whole step a chip, operations of 200 us or more, every kernel
and every collective)."""
import os
import time

import jax
import pytest

from benchmark import harness, xplane

TRACES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traces")

# Chip 0: fusion [0,10) us, all-reduce [8,20) us (2 us hidden behind the
# fusion, 10 us exposed), attn kernel [30,40) us.  Chip 1: fusion [0,40) us
# and an all-reduce [10,20) us wholly hidden.  Host span bench/x [18,32) us.
HAND = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 9 offset_ps: 0 duration_ps: 40000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 8000000 duration_ps: 12000000 }
    events { metadata_id: 3 offset_ps: 30000000 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.7 = f32[8,8]{1,0} fusion(%p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%all-reduce.3 = f32[8]{0} all-reduce(%g)" } }
  event_metadata { key: 3 value { id: 3 name: "%attn.2 = (f32[1,2,8,4]{3,2,1,0}) custom-call(%q), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 9 value { id: 9 name: "jit_step(123)" } }
}
planes { id: 2 name: "/device:TPU:1"
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.7 = f32[8,8]{1,0} fusion(%p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%all-reduce.3 = f32[8]{0} all-reduce(%g)" } }
}
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 18000000 duration_ps: 14000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench/x" } }
}
'''


@pytest.fixture(scope="module")
def hand():
    from jax.profiler import ProfileData

    return xplane.reduce(
        ProfileData.text_proto_to_serialized_xspace(HAND), 2)


def test_busy_union_and_idle_share(hand):
    # chip 0 busy [0,20) + [30,40) = 30 us; chip 1 busy 40 us; window 40 us
    assert hand.window_s == pytest.approx(40e-6)
    assert hand.busy_s == pytest.approx((30e-6 + 40e-6) / 2)
    assert hand.idle_share == pytest.approx(1 - 35 / 40)


def test_exposed_collective_overlap(hand):
    # chip 0: 12 us of all-reduce, 2 hidden -> 10 exposed; chip 1: 0
    assert hand.exposed_seconds() == pytest.approx((10e-6 + 0) / 2)
    assert hand.op_seconds(xplane.COLLECTIVE) == pytest.approx(
        (12e-6 + 10e-6) / 2)
    # by the operation's own name: one that reads an all-reduce is none
    assert xplane.COLLECTIVE.search("%all-reduce-start.4 = f32[8] all-red")
    assert not xplane.COLLECTIVE.search(
        "%convert_fusion.2 = bf16[8]{0} fusion(f32[8]{0} %all-reduce.3)")


def test_kernel_time_by_name(hand):
    runs, calls, seconds = hand.ops_in_module_runs(
        r"^jit_step\b", r'^%?attn[\w.\-]* = .*tpu_custom_call')
    assert (runs, calls) == (1, 1)
    assert seconds == pytest.approx(10e-6)
    assert hand.module_runs(r"^jit_step")[0] == 1


def test_breakdown_names_and_gaps(hand):
    b = hand.breakdown()
    assert b["device_ops"][0][0] == "fusion f32[8,8]"
    assert b["device_ops"][0][1] == pytest.approx((10e-6 + 40e-6) / 2)
    # chip 0's one idle gap, [20,30) us, lies inside bench/x
    assert b["idle_gaps"] == [["bench/x", pytest.approx(10e-6)]]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_stable_names():
    assert xplane.stable_name("%fusion.123 = f32[2] fusion(x)") == "fusion"
    assert xplane.stable_name("all-reduce-start.4") == "all-reduce-start"
    assert xplane.op_label(
        "%copy.9 = bf16[4,8]{1,0:T(8,128)} copy(%x)") == "copy bf16[4,8]"


def test_union_and_subtract():
    merged, total = xplane.union([(5, 7), (0, 3), (2, 4), (7, 9)])
    assert merged == [[0, 4], [5, 9]] and total == 8
    assert xplane.subtract([[0, 10]], [[2, 3], [5, 12]]) == 2 + 2 + 0


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(TRACES) if f.endswith(".xplane.pb")))
def test_recorded_cut(name):
    """A cut of a trace recorded on the v5e reduces to sane numbers: the
    chip is busy most of a training step, the flash kernels are found by
    name two or three to a layer and step, exposed collective time is
    within the collectives' own time."""
    red = xplane.reduce(os.path.join(TRACES, name))
    assert red.chips and red.window_s > 0
    assert 0 < red.busy_s <= red.window_s
    assert 0 <= red.idle_share < 1
    runs, calls, seconds = red.ops_in_module_runs(
        r"^jit_step\b",
        r'^%?(?:attn|shard_map)[\w.\-]* = .*tpu_custom_call')
    # a forward and a backward kernel a layer, two backward before PR 25
    assert runs >= 1 and calls in (runs * 24 * 2, runs * 24 * 3)
    assert seconds > 0
    assert red.exposed_seconds() <= red.op_seconds(xplane.COLLECTIVE) + 1e-12
    if len(red.chips) > 1:
        assert red.op_seconds(xplane.COLLECTIVE) > 0
    assert any(n.startswith(("attn ", "shard_map "))
               for n, _ in red.top_ops())
    assert red.idle_gaps()[0][0].startswith("bench/")


def traced_run(cell, chips):
    """A run of ``cell`` that has traced the recorded cut of its name."""
    run = harness.Run(harness.Cell(cell), 1, 1.0, True,
                      jax.devices()[:chips], time.perf_counter())
    run.peaks = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    run.trace_reduction = xplane.reduce(
        os.path.join(TRACES, cell + ".cut.xplane.pb"), chips)
    run.counters.update(global_batch=4 * chips, seq_len=1024)
    return run, lambda metric: harness._load_reader(run.cell, metric)(run)


def test_one_chip_cut_reads_what_it_read(capsys):
    """The kernel is ``%attn`` on one chip: 72 calls a step, 21.37 % of a
    roofline bound by bytes (the trace is older than PR 25's fused
    backward kernel); no second chip, so no exposed collective."""
    run, read = traced_run("train.gpt2-medium.1chip", 1)
    assert read("flash_attention_roofline") == pytest.approx(21.36794, abs=1e-4)
    assert "144 calls in 2 steps" in capsys.readouterr().out
    assert read("collective_exposed_pct") is None
    assert read("device_idle_pct.train") == pytest.approx(28.116, abs=1e-2)


def test_four_chip_cut_finds_the_kernel_and_the_exposed_all_reduce(capsys):
    """Under ``shard_map`` XLA names the Pallas call ``%shard_map``; the
    reader finds it by either name and by ONE CHIP'S rows in its first
    result, and reads what the one-chip cell reads of the same kernel.  A
    kernel over the global 16 rows is not this chip's and is not read."""
    run, read = traced_run("train.gpt2-medium.dp4", 4)
    assert len(run.trace_reduction.chips) == 4
    share = read("flash_attention_roofline")
    assert "48 calls in 1 steps" in capsys.readouterr().out
    assert share == pytest.approx(35.016, abs=0.01)     # one chip: 36.09
    # twelve all-reduces a step, 26.5 ms of 125.2, nothing beside them
    red = run.trace_reduction
    assert red.exposed_seconds() == pytest.approx(
        red.op_seconds(xplane.COLLECTIVE))
    assert read("collective_exposed_pct") == pytest.approx(21.164, abs=0.01)
    run.counters["global_batch"] = 64
    assert read("flash_attention_roofline") is None
