"""Expert-parallel MoE in the schedule IR (docs/schedule-ir.md "MoE").

Three layers, mirroring the acceptance criteria:

* **builder units** — dispatch/combine ``all_to_all`` pairs per expert
  stack (per microbatch slot under accumulation), honest capacity-
  buffer wire bytes (quantized wire included), ``act:``/``expert:``
  namespaces, JSON/fingerprint round-trip, and fingerprint neutrality
  for non-MoE programs;
* **mutation goldens** — swapped dispatch/combine signatures across
  stages, a missing combine leg, a dropped dispatch→combine ordering
  edge, mismatched per-stage a2a sequences, and an under-provisioned
  capacity config are each rejected/flagged with their distinct rule
  id;
* **wiring** — the analysis pass surfaces ``moe/capacity-overflow``
  with a fix string, the collectives pass re-surfaces cross-stage a2a
  mismatches, and ``estimate_ir_cost`` prices a2a legs per-kind.
"""
import dataclasses

import numpy as np
import pytest

from autodist_tpu.kernel.synchronization import quant_ring
from autodist_tpu.kernel.synchronization import schedule_ir as sir

pytestmark = [pytest.mark.schedule, pytest.mark.moe]


def _moe(key="layers_0/moe", *, stage="", seq=1024, e=8, cf=2.0,
         comp="NoneCompressor", groups=2, d_model=64):
    return sir.MoEFact(key=key, groups=groups, seq=seq, d_model=d_model,
                       num_experts=e, capacity_factor=cf, stage=stage,
                       compressor=comp)


def _fact(name="dense/w", stage=""):
    return sir.PlanFact(name=name, shape=(64, 64), dtype="float32",
                        sync_kind="AllReduce")


def _ir(moe, *, axes=None, accum=1, facts=None):
    return sir.ir_from_facts(
        facts if facts is not None else [_fact()],
        axes=axes or {"data": 2, "expert": 4}, accum_steps=accum,
        moe=moe)


def _with_legs(ir, legs):
    clone = sir.ScheduleIR.from_dict(ir.to_dict())
    clone.legs = list(legs)
    return clone


def _errors(ir):
    return [v for v in sir.verify(ir) if v.severity == sir.SEV_ERROR]


def _rules(violations):
    return {v.rule for v in violations}


def _a2a(ir):
    return [l for l in ir.legs if l.kind == sir.LEG_ALL_TO_ALL]


# -- builder ------------------------------------------------------------------

def test_builder_emits_dispatch_combine_pair_with_namespaces():
    ir = _ir([_moe()])
    legs = _a2a(ir)
    assert [l.id for l in legs] == ["moe/layers_0/moe/dispatch",
                                    "moe/layers_0/moe/combine"]
    disp, comb = legs
    assert disp.reads == ("act:layers_0/moe",)
    assert disp.writes == ("expert:layers_0/moe",)
    assert comb.reads == ("expert:layers_0/moe",)
    assert comb.writes == ("act:layers_0/moe",)
    assert disp.id in comb.deps              # combine waits for dispatch
    assert disp.axis == comb.axis == "expert"
    assert sir.MOE_ROLE_DISPATCH in disp.sig
    assert sir.MOE_ROLE_COMBINE in comb.sig
    assert not sir.verify(ir)


def test_builder_wire_bytes_are_capacity_buffer_shard():
    """Leg nbytes = the per-device [E, G, C, M] capacity buffer — the
    exact tensor the runtime's dispatch einsum materializes and GSPMD
    re-slices across the expert axis."""
    mf = _moe(seq=1024, e=8, cf=2.0, groups=2, d_model=64)
    assert mf.capacity() == 256              # max(1, int(2.0*1024/8))
    elems = 8 * 2 * 256 * 64 // 4            # [E,G,C,M] / axis size
    assert mf.payload_elems(4) == elems
    (disp, comb) = _a2a(_ir([mf]))
    assert disp.nbytes == comb.nbytes == elems * 4


def test_builder_quantized_wire_prices_payload_plus_scales():
    full, quant = _moe(), _moe(comp="Int8Compressor")
    fmt = quant_ring.wire_format_of("Int8Compressor")
    elems = full.payload_elems(4)
    assert quant.leg_nbytes(4) == quant_ring.wire_nbytes(elems, fmt)
    assert quant.leg_nbytes(4) < full.leg_nbytes(4) // 3
    ir = _ir([quant])
    assert all(l.compressor == "Int8Compressor" for l in _a2a(ir))
    assert not _errors(ir)                   # stateless wire: pair is legal


def test_builder_accum_emits_per_slot_pairs_and_chains():
    ir = _ir([_moe()], accum=3)
    legs = _a2a(ir)
    assert len(legs) == 6                    # 3 slots x (dispatch, combine)
    assert sorted({l.slot for l in legs}) == [0, 1, 2]
    assert not sir.verify(ir)                # chained slots: race-free


def test_builder_skips_degenerate_expert_axis():
    assert not _a2a(_ir([_moe()], axes={"data": 8}))
    assert not _a2a(_ir([_moe()], axes={"data": 4, "expert": 1}))


def test_json_roundtrip_and_fingerprint_neutrality():
    ir = _ir([_moe(), _moe("layers_1/moe", comp="Int8Compressor")])
    clone = sir.ScheduleIR.from_json(ir.to_json())
    assert clone.fingerprint() == ir.fingerprint()
    assert clone.moe == ir.moe
    # a program without MoE facts serializes without a moe key at all,
    # so every pre-MoE fingerprint in the wild is preserved
    plain = _ir([])
    assert "moe" not in plain.to_dict()
    assert plain.fingerprint() == _ir(()).fingerprint()
    # and the MoE facts are fingerprint-relevant
    assert _ir([_moe()]).fingerprint() != plain.fingerprint()
    assert _ir([_moe(cf=1.5)]).fingerprint() != \
        _ir([_moe(cf=2.0)]).fingerprint()


def test_capacity_rule_matches_runtime_formula():
    # mirrors parallel/moe.py: capacity = max(1, int(cf * s / e))
    assert sir.moe_capacity_drop_fraction(2.0, 1024, 8) == 0.0
    assert sir.moe_capacity_drop_fraction(1.0, 1024, 8) == 0.5
    assert abs(sir.moe_capacity_drop_fraction(0.5, 1024, 8) - 0.75) < 1e-9
    assert sir.moe_capacity_drop_fraction(2.0, 1, 64) == 0.0  # floor of 1


# -- mutation goldens: each with its distinct rule id -------------------------

def _two_stage_ir():
    """Two pipeline stages, one expert stack each — the cross-stage
    sequence checker compares their a2a issue streams."""
    facts = [_fact("stage0/w"), _fact("stage1/w")]
    moe = [_moe("stage0/moe", stage="stage0"),
           _moe("stage1/moe", stage="stage1")]
    ir = sir.ir_from_facts(facts, axes={"data": 2, "expert": 4}, moe=moe)
    assert len(_a2a(ir)) == 4
    assert not _errors(ir)
    return ir


def test_mutation_swapped_dispatch_combine_across_stages():
    """stage1 issues combine before dispatch while stage0 keeps the
    dispatch-first order: the stages' collective issue streams diverge
    and the a2a deadlocks — caught by the cross-stage sequence rule
    (the a2a deadlock lint), role carried in the leg sig."""
    ir = _two_stage_ir()
    legs = list(ir.legs)
    idx = {l.id: i for i, l in enumerate(legs)}
    a, b = idx["moe/stage1/moe/dispatch"], idx["moe/stage1/moe/combine"]
    legs[a], legs[b] = (
        dataclasses.replace(legs[a], sig=legs[b].sig),
        dataclasses.replace(legs[b], sig=legs[a].sig))
    bad = _with_legs(ir, legs)
    assert sir.RULE_COLLECTIVE_MISMATCH in _rules(_errors(bad))


def test_mutation_missing_combine_leaks_expert_buffer():
    """Dropping a combine leg leaves the capacity buffer written and
    never consumed: dead dispatch work, flagged as a buffer leak."""
    ir = _ir([_moe(stage="moe0")])
    legs = [l for l in ir.legs if l.id != "moe/layers_0/moe/combine"]
    bad = _with_legs(ir, legs)
    leaks = [v for v in sir.verify(bad)
             if v.rule == sir.RULE_BUFFER_LEAK]
    assert leaks and any(v.location == "expert:layers_0/moe"
                         for v in leaks)


def test_mutation_dropped_dispatch_combine_edge_races():
    """Severing the dispatch→combine ordering edge leaves the combine
    reading the capacity buffer the dispatch writes with no
    happens-before path: a read-write race."""
    ir = _ir([_moe(stage="moe0")])
    legs = [dataclasses.replace(l, deps=())
            if l.id == "moe/layers_0/moe/combine" else l
            for l in ir.legs]
    bad = _with_legs(ir, legs)
    errs = _errors(bad)
    assert sir.RULE_RACE_READ_WRITE in _rules(errs)
    assert any(v.location == "expert:layers_0/moe" for v in errs
               if v.rule == sir.RULE_RACE_READ_WRITE)


def test_mutation_mismatched_per_stage_a2a_sequences():
    """stage0 runs two expert layers, stage1 only one: the stages'
    collective counts diverge — ranks in stage1 never post the second
    pair and the all_to_all hangs the step."""
    facts = [_fact("stage0/w"), _fact("stage1/w")]
    moe = [_moe("stage0/moe_a", stage="stage0"),
           _moe("stage0/moe_b", stage="stage0"),
           _moe("stage1/moe_a", stage="stage1")]
    ir = sir.ir_from_facts(facts, axes={"data": 2, "expert": 4}, moe=moe)
    errs = _errors(ir)
    assert sir.RULE_COLLECTIVE_MISMATCH in _rules(errs)


def test_mutation_capacity_overflow_config_warns():
    """An under-provisioned capacity_factor is flagged from the IR
    facts alone — WARN severity (the schedule still executes; tokens
    drop to the residual path)."""
    ir = _ir([_moe(cf=1.0)])
    hits = [v for v in sir.verify(ir)
            if v.rule == sir.RULE_CAPACITY_OVERFLOW]
    assert len(hits) == 1
    assert hits[0].severity == sir.SEV_WARN
    assert "50" in hits[0].message           # drop fraction rendered
    assert not _errors(ir)                   # WARN, not ERROR
    assert not [v for v in sir.verify(_ir([_moe(cf=2.0)]))
                if v.rule == sir.RULE_CAPACITY_OVERFLOW]


def test_mutation_rule_ids_are_distinct():
    """The four golden mutations map to four distinct rule ids."""
    assert len({sir.RULE_COLLECTIVE_MISMATCH, sir.RULE_BUFFER_LEAK,
                sir.RULE_RACE_READ_WRITE,
                sir.RULE_CAPACITY_OVERFLOW}) == 4


# -- wiring -------------------------------------------------------------------

def test_analysis_pass_surfaces_capacity_overflow_with_fix():
    import jax.numpy as jnp

    from autodist_tpu.analysis.analyzer import analyze
    from autodist_tpu.graph_item import GraphItem
    from autodist_tpu.strategy import AllReduce
    from autodist_tpu.resource_spec import ResourceSpec

    gi = GraphItem(
        {"layers_0": {"moe": {"wi": jnp.zeros((8, 16, 32)),
                              "wo": jnp.zeros((8, 32, 16))}}},
        expert_vars=("*/moe/wi", "*/moe/wo"))
    spec = ResourceSpec(resource_info={
        "nodes": [{"address": "localhost", "chips": 8, "chief": True}],
        "mesh": {"data": 2, "expert": 4}})
    strategy = AllReduce().build(gi, spec)
    import os
    old = os.environ.get("AUTODIST_MOE_CAPACITY_FACTOR")
    os.environ["AUTODIST_MOE_CAPACITY_FACTOR"] = "1.0"
    try:
        report = analyze(strategy, gi, resource_spec=spec)
    finally:
        if old is None:
            os.environ.pop("AUTODIST_MOE_CAPACITY_FACTOR", None)
        else:
            os.environ["AUTODIST_MOE_CAPACITY_FACTOR"] = old
    hits = [d for d in report.diagnostics
            if d.rule == sir.RULE_CAPACITY_OVERFLOW]
    assert hits and hits[0].fix_hint
    assert "capacity_factor" in hits[0].fix_hint


def test_estimate_ir_cost_prices_a2a_per_kind():
    from autodist_tpu.strategy.cost_model import estimate_ir_cost

    ir = _ir([_moe()])
    report = estimate_ir_cost(ir)
    assert "all_to_all" in report.per_kind
    assert report.per_kind["all_to_all"] > 0
    # wire bytes: each device ships (d-1)/d of its capacity shard, both
    # directions of the pair
    nb = _a2a(ir)[0].nbytes
    expected = 2 * nb * 3 / 4
    assert abs(report.exposed_wire_bytes
               - (expected + _wire_excluding_a2a(ir))) < 1e-6


def _wire_excluding_a2a(ir):
    from autodist_tpu.strategy import cost_model as cm

    return sum(
        cm._leg_wire_bytes(l, int(ir.axes.get(l.axis, 1)))
        for l in ir.legs if l.kind in sir.COLLECTIVE_KINDS
        and l.kind != sir.LEG_ALL_TO_ALL)


def test_unfitted_a2a_borrows_all_reduce_constants():
    """A calibration fitted before MoE existed prices a2a legs with the
    all_reduce constants (the ps_exchange borrowing rule) instead of
    silently free."""
    from autodist_tpu.strategy.cost_model import leg_cost_s
    from autodist_tpu.telemetry.calibration import LegCalibration

    cal = LegCalibration()
    cal.bandwidths["all_reduce"] = 1e9
    cal.alphas["all_reduce"] = 1e-5
    ir = _ir([_moe()])
    (disp, _) = _a2a(ir)
    got = leg_cost_s(disp, ir, constants=cal)
    assert got > 1e-5                        # alpha + bytes/bw, not zero
    np.testing.assert_allclose(
        got, 1e-5 + disp.nbytes * (3 / 4) / 1e9, rtol=1e-6)


# -- CLI end-to-end smoke ----------------------------------------------------

def test_cli_moe_dump_ir_renders_a2a_legs():
    """``python -m autodist_tpu.analysis moe ... --dump-ir json
    --watermark`` lowers the builtin MoE demo model to a schedule whose
    JSON dump carries the dispatch/combine a2a pairs and their
    ``act:``/``expert:`` namespaces."""
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "autodist_tpu.analysis", "moe",
         "AllReduce", "--mesh", "data=2,expert=4", "--dump-ir", "json",
         "--watermark"],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-2000:]
    payload = json.loads(proc.stdout)
    legs = payload["schedule_ir"]["legs"]
    a2a = [l for l in legs if l["kind"] == sir.LEG_ALL_TO_ALL]
    assert len(a2a) >= 2 and len(a2a) % 2 == 0
    assert {l["axis"] for l in a2a} == {"expert"}
    reads = {r for l in a2a for r in l["reads"]}
    writes = {w for l in a2a for w in l["writes"]}
    assert any(r.startswith("act:") for r in reads)
    assert any(w.startswith("expert:") for w in writes)
    # the watermark simulation saw the capacity transients
    assert payload["watermark"]["peak_bytes"] > 0


def test_cli_moe_watermark_exits_1_on_planted_over_budget_capacity():
    """Planting a huge token count (``AUTODIST_MOE_TOKENS``) against a
    tiny ``--budget-gb`` makes the capacity transients blow the HBM
    budget: the CLI exits 1 and names an ``expert:`` buffer."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               AUTODIST_MOE_TOKENS=str(1 << 22))
    proc = subprocess.run(
        [sys.executable, "-m", "autodist_tpu.analysis", "moe",
         "AllReduce", "--mesh", "data=2,expert=4", "--watermark",
         "--budget-gb", "0.001"],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 1, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "EXCEEDED" in proc.stdout
    assert "expert:" in proc.stdout


# -- a live session's IR, mode against mode -----------------------------------

@pytest.fixture(scope="module")
def session_modes():
    """The IR a live ``moe_transformer_lm`` session carries under three
    modes (the data-only mesh, the expert mesh, the expert mesh on the
    int8 wire): a2a leg count, a2a wire bytes, watermark peak."""
    import jax
    import optax

    from autodist_tpu.analysis import dataflow
    from autodist_tpu.autodist import AutoDist, \
        _reset_default_autodist_for_testing
    from autodist_tpu.mesh import build_mesh
    from autodist_tpu.models.moe_lm import moe_transformer_lm
    from autodist_tpu.strategy import Parallax

    rows = {}
    with pytest.MonkeyPatch.context() as mp:
        for name, axes, wire in (
                ("dense", {"data": 8}, ""),
                ("expert", {"data": 2, "expert": 4}, ""),
                ("expert_int8", {"data": 2, "expert": 4}, "int8")):
            mp.setenv("AUTODIST_MOE_WIRE", wire)
            _reset_default_autodist_for_testing()
            mesh = build_mesh(axes)
            spec = moe_transformer_lm(
                mesh, vocab_size=64, num_layers=2, num_heads=2, head_dim=8,
                d_ff=32, num_experts=4, max_len=16, seq_len=16)
            ad = AutoDist(strategy_builder=Parallax(), mesh_axes=axes)
            with ad.scope():
                ad.capture(params=spec.init(jax.random.PRNGKey(0)),
                           optimizer=optax.adam(1e-3),
                           loss_fn=spec.loss_fn,
                           sparse_vars=spec.sparse_vars,
                           expert_vars=spec.expert_vars)
            ir = ad.create_distributed_session(mesh=mesh).schedule_ir
            assert not _errors(ir)
            rows[name] = {
                "n_a2a_legs": len(_a2a(ir)),
                "a2a_wire_bytes": sum(l.nbytes for l in _a2a(ir)),
                "watermark_peak": dataflow.watermark(ir).peak_bytes}
    _reset_default_autodist_for_testing()
    return rows


def test_session_a2a_legs_only_on_the_expert_mesh(session_modes):
    assert session_modes["dense"]["n_a2a_legs"] == 0
    # 2 expert layers x (dispatch, combine), whatever the wire
    assert session_modes["expert"]["n_a2a_legs"] == 4
    assert session_modes["expert_int8"]["n_a2a_legs"] == 4


def test_session_int8_a2a_wire_at_most_half_of_f32(session_modes):
    assert 0 < session_modes["expert_int8"]["a2a_wire_bytes"] \
        <= session_modes["expert"]["a2a_wire_bytes"] // 2


def test_session_watermark_sees_capacity_buffers(session_modes):
    assert session_modes["expert"]["watermark_peak"] \
        > session_modes["dense"]["watermark_peak"]
