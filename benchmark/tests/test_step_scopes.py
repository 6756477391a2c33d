"""``benchmark/step_scopes.py`` and the six ``step_*_device_pct`` readers,
against the cuts of recorded traces under ``traces_scoped/`` (kanana's
from PR 26 and keye's from PR 32, whose programs knew the attention and
expert scopes alone, as every parent of PR 38 does; smallthinker's from
PR 38's traced run of seed 3700000101, which has the whole vocabulary) and
against a hand-made trace for the rules."""
import os
import re
import time

import jax
import pytest
from jax.profiler import ProfileData

from benchmark import harness, step_scopes, xplane
from benchmark.metrics import moe_routed_device_pct as routed

HERE = os.path.dirname(os.path.abspath(__file__))
KANANA = "train.kanana-2-30b-a3b.ep8-share.seq4096"
KEYE = "train.keye-vl-2.0-30b-a3b.ep8-share.seq16384"
SMALLTHINKER = "train.smallthinker-21b-a3b.ep8-share.seq16384"
METRICS = ("step_unscoped_device_pct", "step_head_loss_device_pct",
           "step_optimizer_device_pct", "step_projection_device_pct",
           "step_layer_carry_device_pct", "step_recomputed_device_pct")

# chip 0, one step program [0,100) us and a program of another name after
# it.  In the step: a conditional's own event [0,30) spanning a routed
# fusion [0,10) and a grouped-product kernel [10,30); an all-reduce [30,50)
# that GSPMD named after the projection whose result it sums; a projection
# recomputed in the backward [50,60); the optimizer [60,70); an operation
# with no tf_op [70,74); a while's own event over all of it.
HAND = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 20 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 21 offset_ps: 100000000 duration_ps: 50000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 8 offset_ps: 0 duration_ps: 74000000 }
    events { metadata_id: 1 offset_ps: 0 duration_ps: 30000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 4 offset_ps: 30000000 duration_ps: 20000000 }
    events { metadata_id: 5 offset_ps: 50000000 duration_ps: 10000000 }
    events { metadata_id: 6 offset_ps: 60000000 duration_ps: 10000000 }
    events { metadata_id: 7 offset_ps: 70000000 duration_ps: 4000000 }
    events { metadata_id: 6 offset_ps: 110000000 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "%cond.3 = (f32[8,8]{1,0}) conditional(%i, %a, %b), branch_computations={%x, %y}" stats { metadata_id: 1 str_value: "jit(step)/jvp(lm/layers)/while/body/closed_call/cond:" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.7 = f32[8,8]{1,0} fusion(%p), kind=kLoop" stats { metadata_id: 1 str_value: "jit(step)/jvp(lm/layers)/while/body/closed_call/moe/route/top_k:" } } }
  event_metadata { key: 3 value { id: 3 name: "%ragged-dot-none.3 = f32[8]{0} custom-call(%g)" stats { metadata_id: 1 str_value: "ragged-dot-none:" } } }
  event_metadata { key: 4 value { id: 4 name: "%all-reduce.5 = f32[8,8]{1,0} all-reduce(%d), replica_groups={}" stats { metadata_id: 1 str_value: "jit(step)/transpose(jvp(lm/layers))/decoder/layers_3/attn/mha/project/query/dot_general:" } } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.9 = f32[8,8]{1,0} fusion(%q), kind=kOutput" stats { metadata_id: 1 str_value: "jit(step)/transpose(jvp(lm/layers))/while/body/closed_call/checkpoint/rematted_computation/gqa/project/dot_general:" } } }
  event_metadata { key: 6 value { id: 6 name: "%fusion.11 = f32[8]{0} fusion(%w), kind=kLoop" stats { metadata_id: 1 str_value: "jit(step)/step/optimizer/add:" } } }
  event_metadata { key: 7 value { id: 7 name: "%copy.2 = f32[8]{0} copy(%w)" } }
  event_metadata { key: 8 value { id: 8 name: "%while.1 = (s32[], f32[8]{0}) while(%t), condition=%c, body=%b" stats { metadata_id: 1 str_value: "jit(step)/jvp(lm/layers)/while:" } } }
  event_metadata { key: 20 value { id: 20 name: "jit_step(123)" } }
  event_metadata { key: 21 value { id: 21 name: "jit_other(7)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
'''


def written(tmp_path, text):
    path = str(tmp_path / "hand.xplane.pb")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    return path


def cut(cell):
    return os.path.join(HERE, "traces_scoped", cell + ".cut.xplane.pb")


def built(path):
    return step_scopes.build(xplane.reduce(path, 1),
                             step_scopes.operation_strings(path))


def traced_run(cell, path, monkeypatch):
    run = harness.Run(harness.Cell(cell), 1, 1.0, True, jax.devices()[:1],
                      time.perf_counter())
    run.trace_reduction = xplane.reduce(path, 1)
    monkeypatch.setattr(run.tracer, "xplane_path", lambda: path)
    return run


def test_the_rules_on_a_hand_made_trace(tmp_path):
    table = built(written(tmp_path, HAND))
    us = {scope: round(s * 1e6, 6) for scope, s in table.by_scope.items()}
    # the conditional's and the while's own events are counted zero times,
    # and the optimizer's second run, outside a step program, not at all
    assert table.steps == 1 and table.program_s == pytest.approx(100e-6)
    assert table.total_s == pytest.approx(74e-6)
    assert us == {"moe/route": 10.0,         # the innermost name wins
                  "moe/experts": 20.0,       # ragged-dot-* by its HLO name
                  "step/collective": 20.0,   # not mha/project
                  "gqa/project": 10.0, "step/optimizer": 10.0,
                  "unscoped": 4.0}
    assert table.recomputed_s == pytest.approx(10e-6)
    assert sum(table.share(s) for s in table.by_scope) \
        == pytest.approx(100.0)
    assert table.share("mha/project", "mla/project") is None
    assert table.share("gqa/project", "mha/project") \
        == pytest.approx(100 * 10 / 74)
    assert table.ops["step/collective"] \
        == {"all-reduce f32[8,8]": pytest.approx(20e-6)}


def test_a_trace_without_a_step_program_reads_none(tmp_path):
    path = written(tmp_path, HAND.replace("jit_step(123)", "jit_fn(1)"))
    assert built(path) is None


@pytest.mark.parametrize("cell,scopes", [
    (KANANA, r"\b(mla/\w+|moe/(?:route|experts|combine|shared))\b"),
    (KEYE, r"\b(dsa/\w+|gqa/project|moe/(?:route|experts|combine))\b"),
    (SMALLTHINKER, r"\b(swa/attention|moe/(?:route|experts|combine))\b"),
])
def test_recorded_steps_split_whole_and_continue_the_series(cell, scopes):
    """The shares sum to 100, and the scopes the cells' accepted readers
    sum read here what ``moe_routed_device_pct.scoped_seconds`` reads of
    them (the ``ragged-dot-*`` kernels under ``moe/experts`` in both)."""
    path = cut(cell)
    table = built(path)
    assert sum(table.share(s) for s in table.by_scope) \
        == pytest.approx(100.0)
    assert sum(table.by_scope.values()) == pytest.approx(table.total_s)
    # the accepted reader's pattern does not know a ``lax.switch``'s own
    # event (``%cond.N``), which spans its branch: taken out of the
    # reduction first, both readers sum the same operations
    red, strings = xplane.reduce(path, 1), routed.operation_strings(path)
    chip = red.chips[0]
    chip.ops = [op for op in chip.ops
                if not re.match(r"%?cond[\w.\-]* = ", op[0])]
    total, by = routed.scoped_seconds(red, strings, scope=re.compile(scopes))
    assert total == pytest.approx(table.total_s)
    assert by and all(
        table.by_scope[scope] == pytest.approx(seconds)
        for scope, seconds in by.items()), (by, table.by_scope)


def test_a_program_before_pr38_reads_as_the_parent_does(monkeypatch, capsys):
    """The benchmark's files are laid over the parent's checkout too: its
    trace has the attention and expert scopes alone.  Nothing raises, what
    has no scope yet is left out of the line, and the rest is a number."""
    run = traced_run(KANANA, cut(KANANA), monkeypatch)
    read = {m: harness._load_reader(run.cell, m)(run) for m in METRICS}
    assert read["step_head_loss_device_pct"] is None
    assert read["step_optimizer_device_pct"] is None
    assert read["step_layer_carry_device_pct"] is None
    assert 15.0 < read["step_unscoped_device_pct"] < 35.0
    assert read["step_projection_device_pct"] == pytest.approx(17.48, abs=.01)
    assert read["step_recomputed_device_pct"] == pytest.approx(18.92, abs=.01)
    # six readers, one parse, one table
    assert capsys.readouterr().out.count("step scopes: 1 whole steps") == 1


def test_a_scoped_step_reads_every_metric(monkeypatch, capsys):
    run = traced_run(SMALLTHINKER, cut(SMALLTHINKER), monkeypatch)
    read = {m: harness._load_reader(run.cell, m)(run) for m in METRICS}
    assert all(isinstance(v, float) for v in read.values()), read
    assert read["step_unscoped_device_pct"] < 8.0
    table = step_scopes.table(run)
    assert set(table.by_scope) <= set(step_scopes.vocabulary()) | {
        step_scopes.UNSCOPED, step_scopes.COLLECTIVE}
    assert read["step_head_loss_device_pct"] == table.share("lm/head_loss")
    assert read["step_layer_carry_device_pct"] == table.share("lm/layers")
    out = capsys.readouterr().out
    assert out.count("whole steps; step program") == 1
    lines = out.splitlines()
    first = next(i for i, ln in enumerate(lines) if "whole steps" in ln)
    assert "moe/route + moe/experts + moe/combine" in lines[-2]
    assert lines[-1].startswith("step scopes: read in ")
    # unscoped stands last among the scopes, under the named ones
    heads = [ln.split(":")[0].strip() for ln in lines[first + 1:]
             if re.match(r"  \S", ln)]
    assert heads.index("unscoped") == len(heads) - 3
