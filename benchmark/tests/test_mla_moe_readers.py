"""The readers that came with the kanana-2-30b-a3b cell, against a cut of
a trace recorded on the v5e (``traces_scoped/``, made by
``cut_trace_scopes.py`` from the traced run of seed 2600000102, PR 26: one
whole step, operations of 100 us or more and every kernel, each with its
``tf_op``) and against a hand-made file for the wire format."""
import os
import time

import jax
import pytest

from benchmark import harness, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train.kanana-2-30b-a3b.ep8-share.seq4096"
CUT = os.path.join(HERE, "traces_scoped", CELL + ".cut.xplane.pb")
PEAKS = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}

# chip 0: a routed fusion [0,10) us, a grouped-product kernel [10,22) us
# whose scope XLA dropped (and whose tf_op is a ref_value), the attention
# kernel [30,35) us; one step program [0,40) us
HAND = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 9 offset_ps: 0 duration_ps: 40000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 12000000 }
    events { metadata_id: 3 offset_ps: 30000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.7 = f32[8,8]{1,0} fusion(%p), kind=kLoop" stats { metadata_id: 3 str_value: "/root/repo/autodist_tpu/parallel/moe.py:261" } stats { metadata_id: 4 str_value: "jit(step)/jit(main)/checkpoint/moe/route/dot_general:" } } }
  event_metadata { key: 2 value { id: 2 name: "%ragged-dot-none.3 = f32[8]{0} custom-call(%g)" stats { metadata_id: 4 ref_value: 5 } stats { metadata_id: 6 int64_value: 77 } } }
  event_metadata { key: 3 value { id: 3 name: "%attn.2 = (f32[1,2,8,4]{3,2,1,0}) custom-call(%q)" stats { metadata_id: 4 str_value: "jit(step)/mla/attention/attn/pallas_call:" } } }
  event_metadata { key: 9 value { id: 9 name: "jit_step(123)" } }
  stat_metadata { key: 3 value { id: 3 name: "source" } }
  stat_metadata { key: 4 value { id: 4 name: "tf_op" } }
  stat_metadata { key: 5 value { id: 5 name: "ragged-dot-none:" } }
  stat_metadata { key: 6 value { id: 6 name: "flops" } }
}
'''


def reader(metric):
    return harness._load_reader(harness.Cell(CELL), metric)


def traced_run(path, monkeypatch):
    run = harness.Run(harness.Cell(CELL), 1, 1.0, True, jax.devices()[:1],
                      time.perf_counter())
    run.peaks = PEAKS
    run.trace_reduction = xplane.reduce(path, 1)
    monkeypatch.setattr(run.tracer, "xplane_path", lambda: path)
    run.counters.update(global_batch=4, seq_len=4096)
    return run


def test_wire_format_reader_finds_tf_op(tmp_path):
    from jax.profiler import ProfileData

    path = str(tmp_path / "hand.xplane.pb")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(HAND))
    mod = reader("moe_routed_device_pct").__globals__
    strings = mod["operation_strings"](path)
    fusion = next(v for k, v in strings.items() if k.startswith("%fusion"))
    assert fusion["tf_op"].endswith("moe/route/dot_general:")
    assert fusion["source"].endswith("moe.py:261")
    grouped = next(v for k, v in strings.items() if "ragged" in k)
    assert grouped == {"tf_op": "ragged-dot-none:"}      # by reference
    total, by = mod["scoped_seconds"](xplane.reduce(path, 1), strings)
    assert total == pytest.approx(27e-6)
    assert by == {"moe/route": pytest.approx(10e-6),
                  "moe/experts": pytest.approx(12e-6)}   # found by name


def test_routed_share_of_the_recorded_step(monkeypatch, capsys):
    """The recorded step spends a third of its device time in route,
    gather, grouped products and combine (the cut keeps operations of
    100 us or more, so the share reads a little higher than the whole
    trace's 33.8 %)."""
    share = reader("moe_routed_device_pct")(traced_run(CUT, monkeypatch))
    assert 30.0 < share < 40.0
    out = capsys.readouterr().out
    for scope in ("moe/route", "moe/experts", "moe/combine"):
        assert scope in out
    assert "moe/shared" not in out


def test_attention_roofline_of_the_recorded_step(monkeypatch, capsys):
    """60 ``attn`` calls a step: a forward, its rematerialised twin and a
    backward for each of 5 layers and 4 sequences; both passes bound by
    FLOPs at Dk 192 / Dv 128; the share under 100 %."""
    run = traced_run(CUT, monkeypatch)
    steps, calls, seconds = run.trace_reduction.ops_in_module_runs(
        r"^jit_step\b", r'^%?attn[\w.\-]* = .*tpu_custom_call')
    assert (steps, calls) == (1, 60)
    share = reader("mla_attention_roofline")(run)
    assert share == pytest.approx(100 * 62.789e-3 / seconds, rel=1e-3)
    assert 35.0 < share < 50.0
    assert "bound by flops forward and flops backward" in \
        capsys.readouterr().out


def test_mfu_and_rows_from_counters(monkeypatch):
    from autodist_tpu.telemetry import registry

    run = traced_run(CUT, monkeypatch)
    run.counters.update(steps=10, tokens_per_step=16384, step_s=[0.8] * 10)
    # 20,480 tokens/s x 2.1607 GFLOP over 197 TFLOP/s
    assert reader("mla_moe_train_mfu_pct")(run) == pytest.approx(
        100 * 20480 * 2.16072192e9 / 197e12)
    registry.reset_for_testing()
    assert reader("moe_rows_computed_per_routed_row")(run) is None
    for kind, rows in (("computed", 393216), ("expected", 49152.0)):
        registry.gauge("autodist_moe_rows_per_step", "", {"kind": kind}
                       ).set(rows)
    assert reader("moe_rows_computed_per_routed_row")(run) == 8.0
    registry.reset_for_testing()
