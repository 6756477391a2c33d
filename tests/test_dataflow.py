"""Schedule dataflow sanitizer: races, leaks, liveness watermark.

Mirrors the PR acceptance criteria (docs/schedule-ir.md "Dataflow"):

* **happens-before units** — the packed-bitset reachability structure
  agrees with brute-force closure on hand and planner graphs;
* **mutation goldens** — a planted unordered write, read-write race,
  buffer leak, donated-``param:``/``opt:`` late read, and watermark
  overflow are each rejected/flagged with their distinct rule id;
* **fuzz** — planner-emitted IRs (incl. fused-kernel legs and
  quantized per-hop chains) show ZERO race/leak findings, and a fuzz
  axis that randomly deletes dep edges must match a brute-force oracle
  exactly: every ordering the deletion breaks between conflicting
  accesses is caught (no false negatives), nothing more is reported
  (no false positives);
* **wiring** — the memory pass's watermark budget rules, beam-search
  OOM pruning (a candidate the coarse footprint sum admitted), the
  tuner's hot-swap veto, elastic preflight on the resized mesh, the
  byte-stable diagnostics ordering, and the CLI
  ``--watermark --dump-ir json`` end-to-end smoke;
* **budget** — verify (races included) + watermark stay under the 1 s
  pre-trace-gate budget on the 9k-leg fixture.
"""
import dataclasses
import json
import os
import subprocess
import sys
import time
from itertools import combinations

import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.analysis import analyze, dataflow
from autodist_tpu.graph_item import GraphItem
from autodist_tpu.kernel.synchronization import bucketing, overlap
from autodist_tpu.kernel.synchronization import schedule_ir as sir
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy.base import Strategy

sys.path.insert(0, os.path.dirname(__file__))
from _analysis_fixtures import AXES8, ar_node  # noqa: E402

pytestmark = pytest.mark.schedule

_MiB = 1 << 20


def _entries(n=6, shape=(256, 256), dtype="float32", comp="NoneCompressor",
             mode="reduce_scatter", prefix="l"):
    return [(f"{prefix}{i}/w", shape, dtype, comp, 0, mode)
            for i in range(n)]


def _ir(entries, *, bucket_bytes=256 << 10, d=8, accum=1, mode="auto",
        guard=False, donated=(), stateful_keys=(), fused_kernels=(),
        moe=(), expert_ax=1):
    buckets = bucketing.assign_buckets(entries, bucket_bytes=bucket_bytes,
                                       shard_divisor=d)
    plan = overlap.resolve_overlap(
        [mode], accum_steps=accum, buckets=buckets, d=d,
        has_rs=any(b.mode == "reduce_scatter" for b in buckets))
    axes = {"data": d}
    if expert_ax > 1:
        axes["expert"] = expert_ax
    return sir.build_schedule_ir(
        axes=axes, accum_steps=accum, buckets=buckets, plan=plan,
        guard=guard, donated=donated, stateful_keys=stateful_keys,
        fused_kernels=fused_kernels, moe=moe)


def _with_legs(ir, legs):
    clone = sir.ScheduleIR.from_dict(ir.to_dict())
    clone.legs = list(legs)
    return clone


def _errors(ir):
    return [v for v in sir.verify(ir) if v.severity == sir.SEV_ERROR]


def _rules(violations):
    return {v.rule for v in violations}


# -- happens-before units -----------------------------------------------------

def _leg(id, deps=(), reads=(), writes=(), kind=sir.LEG_UPDATE, **kw):
    return sir.Leg(id=id, kind=kind, deps=tuple(deps), reads=tuple(reads),
                   writes=tuple(writes), **kw)


def test_happens_before_bitset_matches_hand_graph():
    legs = [_leg("a"), _leg("b", deps=("a",)), _leg("c", deps=("b",)),
            _leg("d")]
    order = sir._topo_order(legs)
    hb = dataflow.HappensBefore(legs, order)
    assert hb.reaches("a", "c") and hb.reaches("a", "b")
    assert not hb.reaches("c", "a")
    assert hb.ordered("a", "c") and not hb.ordered("a", "d")
    assert not hb.reaches("a", "a")


def _brute_force_reach(legs):
    adj = {l.id: [] for l in legs}
    for l in legs:
        for d in l.deps:
            if d in adj:
                adj[d].append(l.id)
    reach = {}
    for src in adj:
        seen, stack = set(), [src]
        while stack:
            for nxt in adj[stack.pop()]:
                if nxt not in seen:
                    seen.add(nxt)
                    stack.append(nxt)
        reach[src] = seen
    return reach


def _oracle_races(ir):
    """Brute-force mirror of the detector's race semantics: the multiset
    of (rule, leg, location) it must report."""
    legs = list(ir.legs)
    reach = _brute_force_reach(legs)

    def ordered(a, b):
        return b in reach[a] or a in reach[b]

    readers, writers = {}, {}
    for l in legs:
        for b in l.reads:
            readers.setdefault(b, []).append(l)
        for b in l.writes:
            writers.setdefault(b, []).append(l)
    out = []
    for buf in writers:
        for a, b in combinations(writers[buf], 2):
            if a.id != b.id and not ordered(a.id, b.id):
                out.append((sir.RULE_RACE_WRITE, min(a.id, b.id), buf))
        for w in writers[buf]:
            for r in readers.get(buf, ()):
                if r.id == w.id or buf in r.writes:
                    continue
                if not ordered(w.id, r.id):
                    out.append((sir.RULE_RACE_READ_WRITE, r.id, buf))
    return sorted(out)


def _detector_races(ir):
    return sorted(
        (v.rule, v.leg, v.location) for v in sir.verify(ir)
        if v.rule in (sir.RULE_RACE_WRITE, sir.RULE_RACE_READ_WRITE))


# -- mutation goldens ---------------------------------------------------------

def test_planner_schedules_have_zero_race_findings():
    ir = _ir(_entries(), d=8, accum=4, guard=True)
    assert not _detector_races(ir)
    assert not [v for v in sir.verify(ir)
                if v.rule == sir.RULE_BUFFER_LEAK]


def test_mutation_planted_unordered_write():
    ir = _ir(_entries(n=2))
    buf = f"red:{ir.buckets[0]['key']}"
    legs = list(ir.legs) + [_leg("rogue-writer", writes=(buf,))]
    bad = _with_legs(ir, legs)
    errs = _errors(bad)
    assert sir.RULE_RACE_WRITE in _rules(errs)
    assert any(v.location == buf for v in errs
               if v.rule == sir.RULE_RACE_WRITE)


def test_mutation_planted_read_write_race():
    ir = _ir(_entries(n=2))
    buf = f"red:{ir.buckets[0]['key']}"
    legs = list(ir.legs) + [_leg("rogue-reader", reads=(buf,))]
    bad = _with_legs(ir, legs)
    errs = _errors(bad)
    assert sir.RULE_RACE_READ_WRITE in _rules(errs)
    assert sir.RULE_RACE_WRITE not in _rules(errs)
    assert any(v.leg == "rogue-reader" for v in errs)


def test_mutation_planted_buffer_leak():
    ir = _ir(_entries(n=1, shape=(8, 8)))
    # drop every reader of the reduced gradient: the reduce is dead work
    buf = f"red:{ir.buckets[0]['key']}"
    legs = [l for l in ir.legs if buf not in l.reads]
    bad = _with_legs(ir, legs)
    leaks = [v for v in sir.verify(bad) if v.rule == sir.RULE_BUFFER_LEAK]
    assert leaks and all(v.severity == sir.SEV_WARN for v in leaks)
    assert any(v.location == buf for v in leaks)


def test_param_and_opt_outputs_are_not_leaks():
    """param:/opt: step outputs are written and never read — by design,
    not a leak."""
    ir = _ir(_entries(n=2), d=8)
    assert not [v for v in sir.verify(ir)
                if v.rule == sir.RULE_BUFFER_LEAK]
    assert any("param:" in b for l in ir.legs for b in l.writes)


def test_read_after_donate_covers_param_and_opt_namespaces():
    ir = _ir(_entries(n=2), d=8)
    key = next(b["key"] for b in ir.buckets
               if b["mode"] == "reduce_scatter")
    for buf in (f"param:{key}", f"opt:{key}"):
        clone = sir.ScheduleIR.from_dict(ir.to_dict())
        clone.donated = (buf,)
        writer = max((l for l in clone.legs if buf in l.writes),
                     key=lambda l: len(l.deps))
        clone.legs = list(clone.legs) + [
            _leg("late-inspect", deps=(writer.id,), reads=(buf,))]
        assert sir.RULE_READ_AFTER_DONATE in _rules(_errors(clone)), buf


# -- fuzz: delete dep edges, compare against the brute-force oracle ----------

_FUZZ_COMPRESSORS = ("NoneCompressor", "HorovodCompressorEF",
                     "Int8Compressor")


def test_fuzz_dep_edge_deletion_matches_oracle():
    """Randomly delete dep edges from planner-emitted IRs — the expert
    axis included (MoE dispatch/combine a2a pairs, multi-layer and
    multi-slot): the race detector must report EXACTLY the conflicting
    pairs whose ordering the deletion broke (brute-force oracle) —
    every mutation the runtime lowering would miscompile is caught, and
    nothing else."""
    rng = np.random.RandomState(20260805)
    caught = 0
    for trial in range(60):
        entries = []
        for i in range(int(rng.randint(1, 5))):
            entries.append(
                (f"v{i}", (int(rng.choice([64, 256])), 64), "float32",
                 str(rng.choice(_FUZZ_COMPRESSORS)), 0,
                 str(rng.choice(["all_reduce", "reduce_scatter"]))))
        expert_ax = int(rng.choice([1, 2, 4]))
        moe = tuple(
            sir.MoEFact(key=f"layers_{j}/moe", groups=2,
                        seq=int(rng.choice([256, 1024])), d_model=64,
                        num_experts=int(rng.choice([4, 8])),
                        capacity_factor=2.0,
                        compressor=str(rng.choice(
                            ["NoneCompressor", "Int8Compressor"])))
            for j in range(int(rng.randint(0, 3))))
        ir = _ir(entries,
                 bucket_bytes=int(rng.choice([16 << 10, 256 << 10])),
                 d=int(rng.choice([2, 4, 8])),
                 accum=int(rng.choice([1, 3])),
                 mode=str(rng.choice(list(overlap.OVERLAP_MODES))),
                 guard=bool(rng.randint(0, 2)),
                 moe=moe, expert_ax=expert_ax)
        legs = list(ir.legs)
        assert _detector_races(ir) == []        # clean before mutation
        for _ in range(int(rng.randint(1, 4))):
            with_deps = [i for i, l in enumerate(legs) if l.deps]
            if not with_deps:
                break
            i = int(rng.choice(with_deps))
            deps = list(legs[i].deps)
            deps.pop(int(rng.randint(len(deps))))
            legs[i] = dataclasses.replace(legs[i], deps=tuple(deps))
        mutated = _with_legs(ir, legs)
        expected = _oracle_races(mutated)
        assert _detector_races(mutated) == expected, trial
        caught += bool(expected)
    # the axis must actually exercise the detector, not only clean runs
    assert caught >= 10


def test_fused_and_quantized_schedules_race_clean():
    """Zero false positives on the PR 11 fused-kernel legs and the PR 8
    quantized per-hop chains."""
    entries = (_entries(n=2, comp="Int8Compressor", mode="all_reduce",
                        prefix="q")
               + _entries(n=2, mode="reduce_scatter", prefix="z"))
    buckets = bucketing.assign_buckets(entries, bucket_bytes=256 << 10,
                                       shard_divisor=8)
    for fused in ((), ("guard",), ("guard", "update", "quant_hop")):
        ir = _ir(entries, d=8, accum=4, mode="full", guard=True,
                 donated=tuple(f"sync:{b.key}" for b in buckets
                               if b.compressor == "Int8Compressor"),
                 stateful_keys=[b.key for b in buckets
                                if b.compressor == "Int8Compressor"],
                 fused_kernels=fused)
        if fused:
            assert any(l.kind in (sir.LEG_FUSED_DETECT,
                                  sir.LEG_FUSED_UPDATE,
                                  sir.LEG_FUSED_HOP) for l in ir.legs)
        errs = _errors(ir)
        assert not errs, (fused, [str(v) for v in errs])
        assert not [v for v in sir.verify(ir)
                    if v.rule == sir.RULE_BUFFER_LEAK]


# -- deterministic diagnostics ordering ---------------------------------------

def test_verify_output_is_sorted_and_stable():
    ir = _ir(_entries(n=2))
    buf = f"red:{ir.buckets[0]['key']}"
    legs = list(ir.legs) + [_leg("rogue-writer", writes=(buf,)),
                            _leg("rogue-reader", reads=(buf,))]
    bad = _with_legs(ir, legs)
    first = [(v.rule, v.leg, v.location, v.message)
             for v in sir.verify(bad)]
    again = [(v.rule, v.leg, v.location, v.message)
             for v in sir.verify(sir.ScheduleIR.from_dict(bad.to_dict()))]
    assert len(first) > 2
    assert first == again
    assert first == sorted(first)


def test_analyze_output_is_stable_across_runs():
    gi = GraphItem({"a": jnp.zeros((64, 64)), "b": jnp.zeros((64, 64))},
                   optimizer=optax.adam(1e-3))
    s = Strategy(node_config=[ar_node("a"), ar_node("b")])
    t1 = analyze(s, gi, mesh=AXES8, budget_bytes=1024).format_table()
    t2 = analyze(s, gi, mesh=AXES8, budget_bytes=1024).format_table()
    assert t1 == t2


# -- the liveness watermark ---------------------------------------------------

def test_watermark_opens_at_write_closes_at_last_read():
    legs = [
        _leg("r1", kind=sir.LEG_ALL_REDUCE, nbytes=10,
             reads=("grad:A",), writes=("red:A",)),
        _leg("u1", deps=("r1",), nbytes=10, reads=("red:A",)),
        _leg("r2", kind=sir.LEG_ALL_REDUCE, deps=("u1",), nbytes=200,
             reads=("grad:B",), writes=("red:B",)),
        _leg("u2", deps=("r2",), nbytes=200, reads=("red:B",)),
    ]
    ir = sir.ScheduleIR(axes={"data": 2}, legs=legs)
    wm = dataflow.watermark(ir)
    # gradients are step inputs (live from t=0); red:A opens at its
    # write (r1) and closes at its last read (u1), so the peak is at
    # r2: grad:B (input) + red:B, with A's buffers all closed.
    assert wm.peak_bytes == 400
    assert wm.peak_leg == "r2"
    assert wm.per_slot[sir.END_OF_STEP] == 400
    # ... and at r1 the A buffers plus the not-yet-consumed grad:B
    # input are live: 10 + 10 + 200 = 220 < 400 (no false peak).
    assert wm.buffer_bytes["grad:B"] == 200


def test_watermark_donation_closes_early():
    def legs():
        return [
            _leg("r1", kind=sir.LEG_ALL_REDUCE, nbytes=10,
                 reads=("grad:A", "sync:A"), writes=("red:A", "sync:A")),
            _leg("u1", deps=("r1",), nbytes=10, reads=("red:A",)),
            _leg("r2", kind=sir.LEG_ALL_REDUCE, deps=("u1",), nbytes=1000,
                 reads=("grad:B",), writes=("red:B",)),
            _leg("u2", deps=("r2",), nbytes=1000, reads=("red:B",)),
        ]
    plain = sir.ScheduleIR(axes={"data": 2}, legs=legs())
    gifted = sir.ScheduleIR(axes={"data": 2}, legs=legs(),
                            donated=("sync:A",))
    wm_plain = dataflow.watermark(plain)
    wm_gifted = dataflow.watermark(gifted)
    # non-donated sync state stays resident to step end (the next step
    # reads it): peak at r2 = sync:A + grad:B + red:B = 2010; donation
    # aliases it away after its last access (r1), so the peak drops.
    assert wm_plain.peak_bytes == 2010 and wm_plain.peak_leg == "r2"
    assert wm_gifted.peak_bytes == 2000
    assert wm_gifted.peak_bytes < wm_plain.peak_bytes


def test_watermark_base_and_pipelined_slots():
    ir = _ir(_entries(), d=8, accum=4)
    wm = dataflow.watermark(ir, base_bytes=1000)
    assert wm.base_bytes == 1000
    assert wm.peak_bytes > 1000
    assert set(wm.per_slot) >= {0, 1, 2, 3}
    d = wm.to_dict()
    assert d["peak_bytes"] == wm.peak_bytes
    assert d["per_slot"] and d["top_buffers"]


def test_watermark_zero1_red_shard_is_fractional():
    """ZeRO-1 reduce-scatter results are 1/d buffers; the all-reduce
    result is full size — the watermark sizes them differently."""
    rs = dataflow.watermark(_ir(_entries(n=1), d=8))
    ar = dataflow.watermark(_ir(_entries(n=1, mode="all_reduce"), d=8))
    key_rs = next(b for b in rs.buffer_bytes if b.startswith("red:"))
    key_ar = next(b for b in ar.buffer_bytes if b.startswith("red:"))
    assert rs.buffer_bytes[key_rs] * 8 == ar.buffer_bytes[key_ar]


def test_watermark_none_on_cyclic_graph():
    legs = [_leg("a", deps=("b",)), _leg("b", deps=("a",))]
    ir = sir.ScheduleIR(axes={"data": 2}, legs=legs)
    assert dataflow.watermark(ir) is None


# -- memory pass / search / tuner / elastic wiring ----------------------------

def _big_gi():
    return GraphItem({"w": jnp.zeros((1024, 1024), jnp.float32)},
                     optimizer=optax.adam(1e-3))


def test_watermark_catches_oom_the_coarse_sum_admitted():
    """THE planted acceptance fixture: params 4 MiB + grads 4 MiB +
    Adam moments 8 MiB = 16 MiB coarse sum fits a 17.5 MiB budget, but
    the schedule's liveness (gradient AND reduce buffer live at the
    reduce leg) peaks at 20 MiB — only the watermark rejects it."""
    gi = _big_gi()
    s = Strategy(node_config=[ar_node("w")])
    budget = int(17.5 * _MiB)
    report = analyze(s, gi, mesh=AXES8, budget_bytes=budget)
    # the coarse sum admitted it...
    msg = report.by_rule("memory/hbm-breakdown")[0].message
    coarse = float(msg.split("≈")[1].split("MiB")[0]) * _MiB
    assert coarse < budget
    # ...the watermark rejects it.
    assert [d.rule for d in report.errors] \
        == ["memory/watermark-exceeds-hbm"]


def test_search_prunes_watermark_oom_before_pricing():
    from autodist_tpu.strategy.search import (
        SYNC_AR,
        VarGene,
        evaluate_candidate,
    )

    gi = _big_gi()
    genes = (("w", VarGene(sync=SYNC_AR)),)

    def spec(hbm_gb):
        return ResourceSpec(resource_info={
            "nodes": [{"address": "localhost", "chips": 8}],
            "hbm_gb": hbm_gb})

    # fact base 12 MiB + grad 4 + red 4 = 20 MiB > 17.5 MiB: pruned
    # BEFORE pricing, with the watermark rule in the verdict.
    ev, strat = evaluate_candidate(
        "planted", genes, gi, spec(17.5 / 1024.0), {"data": 8})
    assert strat is None and ev.cost_s is None
    assert "memory/watermark-exceeds-hbm" in ev.pruned_by
    # a generous budget admits and prices the same candidate.
    ev2, strat2 = evaluate_candidate(
        "planted", genes, gi, spec(16.0), {"data": 8})
    assert ev2.pruned_by is None and ev2.cost_s is not None


def test_beam_search_routes_around_oom_candidates():
    """With a budget only sharded-state schedules fit, the search still
    returns a winner — and it is NOT a replicated-moment AR plan."""
    from autodist_tpu.strategy.search import SearchSpace, beam_search

    gi = _big_gi()
    spec = ResourceSpec(resource_info={
        "nodes": [{"address": "localhost", "chips": 8}],
        "hbm_gb": 17.5 / 1024.0})
    result = beam_search(
        gi, spec, space=SearchSpace(max_rounds=1, max_evals=40,
                                    wall_budget_s=15.0))
    assert result.best is not None
    assert any("memory/watermark-exceeds-hbm" in (e.pruned_by or "")
               for e in result.pruned)
    (_, gene), = result.best.genes
    assert not (gene.sync == "ar")


def test_tuner_watermark_veto():
    from autodist_tpu.strategy.tuner import ScheduleTuner

    gi = _big_gi()
    strat = Strategy(node_config=[ar_node("w")])
    tiny = ResourceSpec(resource_info={
        "nodes": [{"address": "localhost", "chips": 8}],
        "hbm_gb": 17.5 / 1024.0})
    why = ScheduleTuner(gi, tiny).watermark_veto(strat, {"data": 8})
    assert why is not None and "watermark" in why
    roomy = ResourceSpec(resource_info={
        "nodes": [{"address": "localhost", "chips": 8}],
        "hbm_gb": 16.0})
    assert ScheduleTuner(gi, roomy).watermark_veto(
        strat, {"data": 8}) is None


def test_elastic_preflight_runs_watermark_on_resized_mesh():
    """The --elastic-from / preflight_elastic path: the watermark is
    re-simulated on the NEW mesh, where the shrunken data axis holds a
    larger optimizer slice — an OOM resume is rejected statically."""
    gi = _big_gi()
    s = Strategy(node_config=[ar_node("w")])
    tiny = ResourceSpec(resource_info={
        "nodes": [{"address": "localhost", "chips": 2}],
        "hbm_gb": 17.5 / 1024.0})
    report = analyze(s, gi, mesh={"data": 2}, resource_spec=tiny,
                     elastic={"from_axes": {"data": 8}})
    assert any(d.rule == "memory/watermark-exceeds-hbm"
               for d in report.errors)
    assert report.by_rule("memory/watermark")


# -- budget -------------------------------------------------------------------

def test_race_detector_and_watermark_hold_verifier_budget():
    """verify() now includes the happens-before closure + race sweep;
    together with the watermark it must stay under the 1 s pre-trace
    budget on the transformer-scale (9k-leg) fixture.  The budget is of
    the code's own time: the best of three by THIS process's CPU clock,
    so that five other workers on the same cores (the tier-1 run) do not
    read as a slower verifier."""
    entries = [(f"blk{i}/w", (512, 512), "float32", "NoneCompressor",
                0, "reduce_scatter") for i in range(256)]
    ir = _ir(entries, bucket_bytes=1 << 20, d=8, accum=4, guard=True)
    assert len(ir.legs) > 9_000
    took = []
    for _ in range(3):
        t0 = time.process_time()
        violations = sir.verify(ir)
        wm = dataflow.watermark(ir)
        took.append(time.process_time() - t0)
        assert not [v for v in violations if v.severity == sir.SEV_ERROR]
        assert wm is not None and wm.peak_bytes > 0
    assert min(took) < 1.0, (f"verify+watermark took {min(took):.2f}s at "
                             f"best of {took} on {len(ir.legs)} legs")


# -- CLI end-to-end smoke (tier-1) -------------------------------------------

def test_cli_watermark_dump_ir_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "autodist_tpu.analysis", "mlp", "Zero1",
         "--mesh", "data=8", "--watermark", "--dump-ir", "json"],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-2000:]
    payload = json.loads(proc.stdout)
    assert payload["schedule_ir"]["legs"]
    wm = payload["watermark"]
    assert wm["peak_bytes"] > 0 and wm["peak_leg"] and wm["per_slot"]


def test_cli_watermark_budget_exit_code(capsys):
    from autodist_tpu.analysis.__main__ import main

    rc = main(["mlp", "Zero1", "--mesh", "data=8", "--watermark",
               "--budget-gb", "0.000001"])
    assert rc == 1
    out = capsys.readouterr().out
    assert "EXCEEDED" in out
    rc = main(["mlp", "Zero1", "--mesh", "data=8", "--watermark",
               "--json"])
    assert rc == 0
