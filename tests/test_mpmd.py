"""MPMD pipeline runtime tests (docs/pipeline.md).

Covers the stage partitioner, the DCN activation transport, the
``send_act``/``recv_act`` schedule-IR legs (tier parity, fingerprint
equality, mutation goldens with DISTINCT rule ids), pipeline pricing
(bubble fraction + exposed DCN activation bytes), stage-filtered chaos,
hang localization naming the wedged stage, the ``stages=`` sweep
dimension, and 2- and 4-stage thread-backed parity runs against the
single-program ``one_f_one_b`` oracle.  The live 2 stages x 2 DP procs
drill (tests/integration/mpmd_train.py) rides at the end under the
``slow`` marker.
"""
import dataclasses
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from autodist_tpu.kernel.synchronization import schedule_ir as sir
from autodist_tpu.parallel import mpmd
from autodist_tpu.parallel.mpmd import transport as tmod
from autodist_tpu.resilience.chaos import ChaosMonkey, parse_chaos
from autodist_tpu.resilience.elastic import ElasticResumeError

pytestmark = pytest.mark.mpmd

L, D = 4, 8
S, M = 2, 4


def _layers(seed=0, l=L, d=D):
    rng = np.random.RandomState(seed)
    return [{"w": (rng.randn(d, d) * 0.3).astype(np.float32),
             "b": (rng.randn(d) * 0.1).astype(np.float32)}
            for _ in range(l)]


def _prog(s=S, m=M, **kw):
    kw.setdefault("act_nbytes", 2 * D * 4)
    return mpmd.build_pipeline_ir(layer_params=_layers(), num_stages=s,
                                  num_microbatches=m, **kw)


def _rules(ir):
    return {v.rule for v in sir.errors(sir.verify(ir))}


# -- satellite 3: ONE stage-name spelling everywhere --------------------------

def test_stage_naming_shared_helper():
    assert sir.stage_name(1) == "stage1"
    assert sir.stage_name(3, "expert") == "expert3"
    assert sir.stage_index("stage7") == 7
    assert sir.stage_of("stage1/l2/w") == "stage1"
    assert sir.stage_of("expert3/up") == "expert3"
    # the partitioner's qualified names parse back through the same
    # helper the verifier and MoEFact use
    part, stages = mpmd.partition_params(_layers(), S)
    for i, sp in enumerate(stages):
        for name in sp:
            assert sir.stage_of(name) == sir.stage_name(i)
    assert part.param_names(0) == tuple(sorted(stages[0]))


def test_chaos_stage_spec_normalizes_through_stage_name():
    # `stage=1` and `stage=stage1` are the same filter
    ev_digit = parse_chaos("kill@step=1,proc=0,stage=1")[0]
    ev_named = parse_chaos("kill@step=1,proc=0,stage=stage1")[0]
    assert ev_digit.stage == ev_named.stage == sir.stage_name(1)


# -- partitioner --------------------------------------------------------------

def test_assign_layers_balanced_front_loaded():
    assert mpmd.assign_layers(4, 2) == ((0, 1), (2, 3))
    # the spare layer goes to the EARLY stage (1F1B memory profile)
    assert mpmd.assign_layers(5, 2) == ((0, 1, 2), (3, 4))
    assert mpmd.assign_layers(7, 3) == ((0, 1, 2), (3, 4), (5, 6))
    with pytest.raises(ValueError, match=sir.RULE_STAGE_MISMATCH):
        mpmd.assign_layers(2, 3)


def test_partition_params_naming():
    part, stages = mpmd.partition_params(_layers(), S)
    assert part.layers == ((0, 1), (2, 3))
    assert sorted(stages[0]) == ["stage0/l0/b", "stage0/l0/w",
                                 "stage0/l1/b", "stage0/l1/w"]
    assert sorted(stages[1]) == ["stage1/l2/b", "stage1/l2/w",
                                 "stage1/l3/b", "stage1/l3/w"]
    assert mpmd.strip_stage("stage1/l2/w") == "l2/w"
    assert mpmd.strip_stage("l2/w") == "l2/w"


def test_restage_roundtrip_lossless():
    layers = _layers()
    _, two = mpmd.partition_params(layers, 2)
    four = mpmd.restage_params(two, 4)
    assert len(four) == 4
    back = mpmd.restage_params(four, 2)
    for a, b in zip(two, back):
        assert sorted(a) == sorted(b)
        for k in a:
            assert np.array_equal(np.asarray(a[k]), np.asarray(b[k]))


def test_restage_torn_save_raises():
    _, two = mpmd.partition_params(_layers(), 2)
    torn = [dict(two[0]), dict(two[1])]
    # layer 2's weight claimed by BOTH stage snapshots: a torn save
    torn[0]["stage0/l2/w"] = two[1]["stage1/l2/w"]
    with pytest.raises(ElasticResumeError, match="torn save"):
        mpmd.restage_params(torn, 2)


def test_stage_mismatch_reason_rule_prefixed():
    assert sir.stage_mismatch_reason(2, 4) is None
    for bad in (sir.stage_mismatch_reason(0, 4),
                sir.stage_mismatch_reason(8, 8, num_layers=4),
                sir.stage_mismatch_reason(4, 2)):
        assert bad is not None and bad.startswith(sir.RULE_STAGE_MISMATCH)
    with pytest.raises(ValueError, match=sir.RULE_STAGE_MISMATCH):
        _prog(s=2, m=1)


def test_preflight_stage_resize():
    prog = _prog()
    meta = {"partition": prog.partition.to_meta(),
            "num_microbatches": M, "act_nbytes": 2 * D * 4}
    new = mpmd.preflight_stage_resize(meta, num_stages=4,
                                      num_microbatches=4)
    assert new.partition.num_stages == 4
    assert new.fingerprint() != prog.fingerprint()
    assert not sir.errors(sir.verify(new.ir))
    with pytest.raises(ElasticResumeError,
                       match=sir.RULE_STAGE_MISMATCH):
        mpmd.preflight_stage_resize(meta, num_stages=8)
    with pytest.raises(ElasticResumeError,
                       match=sir.RULE_STAGE_MISMATCH):
        mpmd.preflight_stage_resize(meta, num_stages=4,
                                    num_microbatches=2)


# -- the IR: tier parity, fingerprints, mutation goldens ----------------------

# (stages, microbatches): the module's own point, then one, two and four
# stages at eight microbatches
STAGE_POINTS = [(S, M), (1, 8), (2, 8), (4, 8)]


@pytest.mark.parametrize("s, m", STAGE_POINTS)
def test_transport_legs_tier_and_shape(s, m):
    prog = _prog(s, m)
    assert not sir.errors(sir.verify(prog.ir))
    transport = [l for l in prog.ir.legs if l.kind in sir.TRANSPORT_KINDS]
    # each of the s-1 boundaries is crossed forward and backward, each
    # crossing m send/recv pairs (none at one stage)
    assert len(transport) == 4 * (s - 1) * m
    stages = {sir.stage_name(i) for i in range(s)}
    for leg in transport:
        assert leg.tier == sir.TIER_DCN
        assert leg.stage in stages
        bufs = leg.writes if leg.kind == sir.LEG_SEND_ACT else leg.reads
        assert len(bufs) == 1 and bufs[0].startswith("act:")
    sends = [l for l in transport if l.kind == sir.LEG_SEND_ACT]
    assert len(sends) == 2 * (s - 1) * m


@pytest.mark.parametrize("s, m", STAGE_POINTS)
def test_fingerprint_static_equals_runtime(s, m):
    prog = _prog(s, m)
    rebuilt = sir.ir_from_facts(list(prog.facts), axes=dict(prog.axes),
                                accum_steps=int(prog.ir.accum_steps),
                                pipeline=list(prog.pipeline))
    assert rebuilt.fingerprint() == prog.ir.fingerprint()
    # the STATIC dedupe key (a hash of the fact INPUTS, not the legs)
    # is deterministic: same facts -> same key -> same program
    assert prog.fingerprint() == _prog(s, m).fingerprint()
    assert _prog(s, 2 * m).fingerprint() != prog.fingerprint()


def test_pre_mpmd_fingerprints_unchanged():
    # a pipeline-free build must hash identically whether or not the
    # (empty) pipeline argument is spelled out — old fingerprints,
    # checkpoints, and goldens stay valid
    facts = [sir.PlanFact(name="w", shape=(64, 64), dtype="float32",
                          sync_kind="AllReduce")]
    a = sir.ir_from_facts(facts, axes={"data": 2})
    b = sir.ir_from_facts(facts, axes={"data": 2}, pipeline=[])
    assert a.fingerprint() == b.fingerprint()
    assert sir.facts_fingerprint(facts, axes={"data": 2}) \
        == sir.facts_fingerprint(facts, axes={"data": 2}, pipeline=[])


def _clone(ir):
    return sir.ScheduleIR.from_dict(ir.to_dict())


def test_mutation_orphaned_recv_is_act_transport():
    clone = _clone(_prog().ir)
    # drop the LAST backward recv at stage0: its send is orphaned
    clone.legs = [l for l in clone.legs
                  if l.id != f"pipe/pipe/b0@{M - 1}/recv"]
    assert sir.RULE_ACT_TRANSPORT in _rules(clone)


def test_mutation_unordered_recv_is_race_read_write():
    clone = _clone(_prog().ir)
    legs = list(clone.legs)
    i = next(k for k, l in enumerate(legs)
             if l.id == "pipe/pipe/f0@0/recv")
    # recv no longer depends on its send: the act: buffer read races
    # the write AND the transport contract breaks
    legs[i] = dataclasses.replace(legs[i], deps=())
    clone.legs = legs
    rules = _rules(clone)
    assert sir.RULE_RACE_READ_WRITE in rules
    assert sir.RULE_ACT_TRANSPORT in rules


def test_mutation_dangling_dep_is_unknown_dep():
    clone = _clone(_prog().ir)
    legs = list(clone.legs)
    i = next(k for k, l in enumerate(legs)
             if l.id == "pipe/pipe/f0@1/send")
    legs[i] = dataclasses.replace(
        legs[i], deps=legs[i].deps + ("pipe/pipe/f9@9/send",))
    clone.legs = legs
    assert sir.RULE_UNKNOWN_DEP in _rules(clone)


def test_mutation_cycle_is_dep_cycle():
    clone = _clone(_prog().ir)
    legs = list(clone.legs)
    first = next(k for k, l in enumerate(legs)
                 if l.kind in sir.TRANSPORT_KINDS)
    legs[first] = dataclasses.replace(
        legs[first], deps=legs[first].deps + (legs[-1].id,))
    clone.legs = legs
    assert sir.RULE_DEP_CYCLE in _rules(clone)


def test_mutation_misordered_send_slots_is_act_transport():
    clone = _clone(_prog().ir)
    legs = list(clone.legs)
    a = next(k for k, l in enumerate(legs)
             if l.id == "pipe/pipe/f0@0/send")
    b = next(k for k, l in enumerate(legs)
             if l.id == "pipe/pipe/f0@1/send")
    # swap the slots WITHOUT moving the legs: the chain's send order no
    # longer matches microbatch order (a mis-sequenced runner)
    legs[a] = dataclasses.replace(legs[a], slot=1)
    legs[b] = dataclasses.replace(legs[b], slot=0)
    clone.legs = legs
    assert sir.RULE_ACT_TRANSPORT in _rules(clone)


# -- pricing: bubble + exposed DCN activation bytes ---------------------------

def test_cost_model_prices_bubble_and_act_bytes():
    from autodist_tpu.strategy.cost_model import (act_transport_bytes,
                                                  estimate_ir_cost)
    prog = _prog()
    report = estimate_ir_cost(prog.ir, compute_time_s=1.0)
    want = sir.bubble_fraction_1f1b(S, M)
    assert report.bubble_fraction == pytest.approx(want)
    assert want == pytest.approx(1 / 3)
    total, exposed = act_transport_bytes(prog.ir)
    assert total > 0
    # 8 send legs total; only the slot M-1 pair is outside the hidden
    # accumulation window
    assert total == pytest.approx(4 * exposed)
    # no pipeline -> no bubble, no activation wire
    flat = sir.ir_from_facts(list(prog.facts), axes=dict(prog.axes),
                             accum_steps=M)
    assert estimate_ir_cost(flat, compute_time_s=1.0) \
        .bubble_fraction == 0.0
    assert act_transport_bytes(flat) == (0.0, 0.0)


# -- transport ----------------------------------------------------------------

def test_transport_inmemory_roundtrip_and_timeout():
    tmod.reset_registry()
    tr = mpmd.ActivationTransport("", channel="dp0", timeout_s=0.2)
    v = np.arange(12, dtype=np.float32).reshape(3, 4)
    tr.send("act:pipe/f0@0", v)
    got = tr.recv("act:pipe/f0@0")
    assert np.array_equal(got, v)
    # channels are disjoint scopes
    other = mpmd.ActivationTransport("", channel="dp1", timeout_s=0.05)
    with pytest.raises(mpmd.TransportTimeout, match="act:pipe/f0@0"):
        other.recv("act:pipe/f0@0")


def test_transport_directory_nonconsuming_and_gc(tmp_path):
    tmod.reset_registry()
    a = mpmd.ActivationTransport(str(tmp_path), channel="dp0",
                                 timeout_s=1.0)
    v = np.ones((4,), np.float32)
    a.send("s2/act:pipe/f0@0", v)
    tmod.reset_registry()   # force the directory path
    b = mpmd.ActivationTransport(str(tmp_path), channel="dp0",
                                 timeout_s=1.0)
    assert np.array_equal(b.recv("s2/act:pipe/f0@0"), v)
    # NON-consuming: a chaos-restarted runner re-reads the same step
    assert np.array_equal(b.recv("s2/act:pipe/f0@0"), v)
    assert b.gc("s2/") >= 1
    with pytest.raises(mpmd.TransportTimeout):
        b.recv("s2/act:pipe/f0@0", timeout_s=0.05)


def test_transport_corrupt_blob_skipped_then_retransmit(tmp_path):
    tmod.reset_registry()
    tr = mpmd.ActivationTransport(str(tmp_path), channel="dp0",
                                  timeout_s=5.0, poll_s=0.005)
    path = tr._path("act:pipe/f0@0")
    with open(path, "wb") as f:
        f.write(b"ADTPUACT1 garbage that fails the digest")
    tmod.reset_registry()
    v = np.full((3,), 7.0, np.float32)

    def retransmit():
        good = mpmd.ActivationTransport(str(tmp_path), channel="dp0")
        good.send("act:pipe/f0@0", v)

    t = threading.Timer(0.1, retransmit)
    t.start()
    try:
        tmod.reset_registry()   # make the recv poll the directory blob
        got = tr.recv("act:pipe/f0@0")
    finally:
        t.join()
    assert np.array_equal(got, v)


# -- chaos: stage= filtering --------------------------------------------------

def _armed_monkey(spec, **kw):
    monkey = ChaosMonkey(parse_chaos(spec), **kw)
    fired = []
    monkey._exit = lambda code: fired.append(code)
    return monkey, fired


def test_chaos_stage_filter_fires_only_on_matching_stage():
    spec = "kill@step=1,proc=0,stage=1,code=43"
    monkey, fired = _armed_monkey(spec, process_index=0, attempt=0,
                                  stage="stage0")
    monkey.on_step(1)
    assert fired == []          # wrong stage: no fire
    monkey, fired = _armed_monkey(spec, process_index=0, attempt=0,
                                  stage="stage1")
    monkey.on_step(0)
    assert fired == []          # right stage, wrong step
    monkey.on_step(1)
    assert fired == [43]


def test_chaos_stage_from_environment(monkeypatch):
    # StageRunner stamps AUTODIST_STAGE; an unconfigured monkey picks
    # the stage identity up from there
    spec = "kill@step=2,stage=0,code=41"
    monkeypatch.setenv("AUTODIST_STAGE", "stage1")
    monkey, fired = _armed_monkey(spec, process_index=0)
    monkey.on_step(2)
    assert fired == []
    monkeypatch.setenv("AUTODIST_STAGE", "stage0")
    monkey, fired = _armed_monkey(spec, process_index=0)
    monkey.on_step(2)
    assert fired == [41]


# -- hang localization names the wedged stage ---------------------------------

def test_localize_hang_names_wedged_stage(tmp_path):
    from autodist_tpu.telemetry import flightrec as fr

    ir = _prog().ir
    recv = "pipe/pipe/f0@0/recv"      # stage1's first fwd input
    later = f"pipe/pipe/b0@{M - 1}/send"
    diag = fr.localize_hang(ir, {
        "stage1/dp0": {"leg": recv, "kind": "leg", "step": 3},
        "stage1/dp1": {"leg": later, "kind": "leg", "step": 3},
    })
    assert diag is not None
    assert diag.frontier_leg == recv
    assert diag.culprits == ("stage1/dp0",)
    assert diag.stage == "stage1"
    assert "wedged at pipeline stage 'stage1'" in diag.detail
    bundle = tmp_path / "bundle"
    bundle.mkdir()
    (bundle / "hang.json").write_text(json.dumps(diag.to_dict()))
    report = fr.render_hang_report(str(bundle))
    assert "wedged stage: stage1" in report
    assert recv in report


# -- the stages= sweep dimension ----------------------------------------------

def test_simulate_sweep_stages_dimension():
    from autodist_tpu.analysis.simulate import (format_sweep_report,
                                                parse_sweep_spec,
                                                run_sweep)
    from autodist_tpu.graph_item import GraphItem
    from autodist_tpu.strategy import AllReduce

    gi = GraphItem({"w": jnp.zeros((256, 256), jnp.float32)})

    def make(spec, hier):
        return (AllReduce(hier=True) if hier else AllReduce()).build(
            gi, spec)

    config = parse_sweep_spec("mesh=data=8;slices=1;dcn=25;"
                              "stages=1,2,8;mb=4;act=1")
    assert config["stages"] == [1, 2, 8]
    report = run_sweep(gi, make, config)
    by_stages = {p["stages"]: p for p in report["points"]}
    assert set(by_stages) == {1, 2, 8}
    # 8 stages cannot run 4 microbatches: pruned BEFORE pricing, with
    # the shared rule id
    assert by_stages[8]["pruned_by"].startswith(sir.RULE_STAGE_MISMATCH)
    piped = by_stages[2]
    assert piped["microbatches"] == 4
    for cell in piped["modes"].values():
        assert cell["bubble_fraction"] == pytest.approx(
            sir.bubble_fraction_1f1b(2, 4))
        assert cell["dcn_act_bytes"]["total"] > 0
        assert cell["dcn_act_bytes"]["exposed"] \
            <= cell["dcn_act_bytes"]["total"]
    # single-stage points carry no pipeline cells
    assert "bubble_fraction" not in \
        next(iter(by_stages[1]["modes"].values()))
    text = format_sweep_report(report)
    assert "stages=2" in text and "bubble" in text


# -- the runner: ZeRO-1 kernel + thread-backed parity drill -------------------

def test_make_zero1_update_degenerate_matches_sgd():
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    upd = mpmd.make_zero1_update(mesh, lr=0.1, num_shards=1)
    p = jnp.arange(8, dtype=jnp.float32)
    g = jnp.ones((8,), jnp.float32)
    out = np.asarray(upd(g[None, :], p))
    assert np.allclose(out, np.asarray(p) - 0.1 * np.asarray(g))


@pytest.mark.parametrize("S", [2, 4])
def test_staged_parity_vs_one_f_one_b_oracle(S):
    """Two and four thread-backed stages step like the single-program
    1F1B loop over the same model, so every stage count agrees with
    every other from step 0 on."""
    from autodist_tpu.mesh import build_mesh
    from autodist_tpu.parallel.pipeline_1f1b import one_f_one_b

    layers = _layers()
    part, stage_params = mpmd.partition_params(layers, S)
    prog = mpmd.build_pipeline_ir(layer_params=layers, num_stages=S,
                                  num_microbatches=M,
                                  act_nbytes=2 * D * 4)

    def stage_fn_for(si):
        def fn(p, x):
            h = x
            for j in part.layers[si]:
                pre = f"{sir.stage_name(si)}/l{j}"
                h = jnp.tanh(h @ p[f"{pre}/w"] + p[f"{pre}/b"])
            return h
        return fn

    def mse(y, t):
        return jnp.mean((y - t) ** 2)

    rng = np.random.RandomState(1)
    B = 8
    x = rng.randn(B, D).astype(np.float32)
    tgt = rng.randn(B, D).astype(np.float32)
    rows = B // M
    x_mbs = [x[i * rows:(i + 1) * rows] for i in range(M)]
    t_mbs = [tgt[i * rows:(i + 1) * rows] for i in range(M)]

    tmod.reset_registry()
    runners = [mpmd.StageRunner(
        prog, si, stage_fn=stage_fn_for(si), params=stage_params[si],
        transport=mpmd.ActivationTransport("", channel="dp0"), lr=0.1,
        loss_fn=mse if si == S - 1 else None) for si in range(S)]

    steps, losses = 3, []
    for _ in range(steps):
        res = [None] * S

        def run(si):
            res[si] = runners[si].run_step(
                x_mbs if si == 0 else None,
                t_mbs if si == S - 1 else None)

        ths = [threading.Thread(target=run, args=(si,))
               for si in range(S)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        losses.append(res[S - 1])

    # oracle: the SAME model as one stacked single-program 1F1B loop
    sp = {"w": np.stack([np.stack([layers[j]["w"] for j in run])
                         for run in part.layers]),
          "b": np.stack([np.stack([layers[j]["b"] for j in run])
                         for run in part.layers])}

    def sfn(p, h):
        for j in range(p["w"].shape[0]):
            h = jnp.tanh(h @ p["w"][j] + p["b"][j])
        return h

    mesh = build_mesh({"pipe": S}, devices=jax.devices()[:S])
    cur = {k: jnp.asarray(v) for k, v in sp.items()}
    oracle = []
    for _ in range(steps):
        loss, grads, _ = one_f_one_b(sfn, mse, cur, jnp.asarray(x),
                                     jnp.asarray(tgt), mesh,
                                     num_microbatches=M)
        cur = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, cur,
                                     grads)
        oracle.append(float(loss))

    assert max(abs(a - b) for a, b in zip(losses, oracle)) <= 1e-5


# -- the live 2 stages x 2 DP procs drill -------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DRILL = os.path.join(REPO, "tests", "integration", "mpmd_train.py")


@pytest.mark.slow
def test_mpmd_live_drill(tmp_path):
    """2 stages x 2 DP procs over the gloo coordinator: loss parity
    <= 1e-5 vs the single-program oracle, and a chaos-killed stage
    worker recovers through the supervisor BIT-EXACT."""
    result_file = tmp_path / "result.json"
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("AUTODIST_")}
    env.update({
        "AUTODIST_REPO_ROOT": REPO,
        "AUTODIST_MPMD_WORKDIR": str(tmp_path / "work"),
        "AUTODIST_RESULT_FILE": str(result_file),
        "PYTHONPATH": REPO,
    })
    proc = subprocess.run([sys.executable, DRILL], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, \
        f"drill failed\nstdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    result = json.loads(result_file.read_text())
    clean, chaos, oracle = (result["clean"], result["chaos"],
                            result["oracle"])
    # parity vs the single-program 1F1B oracle
    assert len(clean["losses"]) == len(oracle["losses"])
    for a, b in zip(clean["losses"], oracle["losses"]):
        assert abs(a - b) <= 1e-5, (clean["losses"], oracle["losses"])
    # the chaos job killed at least one stage worker and recovered
    assert chaos["restarts"] >= 1
    # ... BIT-exact: same losses, same final parameter checksums
    assert chaos["losses"] == clean["losses"]
    assert chaos["checksums"] == clean["checksums"]
    # one schedule fingerprint across every process of every attempt
    assert len(set(clean["fingerprints"])) == 1
    assert set(chaos["fingerprints"]) == set(clean["fingerprints"])
