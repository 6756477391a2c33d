"""Hierarchical ICI+DCN grad sync (docs/strategies.md "Two-tier sync
and --simulate").

Runtime parity of the two-level lowering (within-slice reduce-scatter,
cross-slice DCN exchange, within-slice all-gather) against the flat
ring on a simulated 2-slice CPU mesh — plain AllReduce and ZeRO-1, f32
exact and int8-DCN within quantizer tolerance; static-vs-runtime
schedule fingerprint equality; the ResourceSpec slice fields and the
``legality/slice-mismatch`` fail-fast; the beam search's ``hier`` gene
flipping flat -> hierarchical when the DCN narrows; the ``--simulate``
sweep (in-process and the CLI subprocess, including the over-HBM
exit-1 contract); and the telemetry compare report with leg kinds
present in only one run.
"""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.autodist import AutoDist, _reset_default_autodist_for_testing
from autodist_tpu.graph_item import GraphItem
from autodist_tpu.kernel.synchronization import schedule_ir as sir
from autodist_tpu.resource_spec import (
    RULE_SLICE_MISMATCH,
    ResourceSpec,
    ResourceSpecError,
    slice_mismatch_reason,
)
from autodist_tpu.strategy import AllReduce, Zero1

pytestmark = pytest.mark.hier

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset(monkeypatch):
    monkeypatch.delenv("AUTODIST_DCN_WIRE", raising=False)
    _reset_default_autodist_for_testing()
    yield
    _reset_default_autodist_for_testing()


def _spec(num_slices=1, dcn_gbps=25):
    info = {"nodes": [{"address": "localhost", "chips": 8,
                       "chief": True}],
            "mesh": {"data": 8}}
    if num_slices > 1:
        info["num_slices"] = num_slices
        info["dcn_gbps"] = dcn_gbps
    return ResourceSpec(resource_info=info)


def _problem():
    rng = np.random.RandomState(3)
    params = {"a": {"w": jnp.asarray(rng.randn(13, 9) * 0.1, jnp.float32),
                    "b": jnp.asarray(rng.randn(9) * 0.1, jnp.float32)},
              "out": {"w": jnp.asarray(rng.randn(9, 4) * 0.1, jnp.float32)}}
    batch = {"x": rng.randn(16, 13).astype(np.float32),
             "y": rng.randn(16, 4).astype(np.float32)}

    def loss_fn(p, b):
        h = jnp.tanh(b["x"] @ p["a"]["w"] + p["a"]["b"])
        return jnp.mean((h @ p["out"]["w"] - b["y"]) ** 2)

    return params, loss_fn, batch


def _session(builder, spec, params, loss_fn):
    _reset_default_autodist_for_testing()
    ad = AutoDist(strategy_builder=builder, resource_spec=spec)
    with ad.scope():
        ad.capture(params=params, optimizer=optax.adam(1e-2),
                   loss_fn=loss_fn)
    return ad, ad.create_distributed_session()


def _assert_parity(flat_builder, hier_builder, tol):
    params, loss_fn, batch = _problem()
    _, flat = _session(flat_builder, _spec(1), params, loss_fn)
    _, hier = _session(hier_builder, _spec(2), params, loss_fn)
    ir = hier.schedule_ir
    kinds = {l.kind for l in ir.legs}
    assert kinds & set(sir.HIER_KINDS), \
        f"no hierarchical legs in the runtime IR: {sorted(kinds)}"
    assert any(l.tier == sir.TIER_DCN for l in ir.legs)
    assert not sir.errors(sir.verify(ir))
    for _ in range(5):
        lf = float(flat.run(batch)["loss"])
        lh = float(hier.run(batch)["loss"])
        np.testing.assert_allclose(lh, lf, rtol=tol, atol=tol)
    for k, leaves in flat.params.items():
        for kk in leaves:
            np.testing.assert_allclose(
                np.asarray(hier.params[k][kk]),
                np.asarray(flat.params[k][kk]), rtol=tol, atol=tol)
    return ir


# -- runtime parity: two-tier lowering == flat ring --------------------------

@pytest.mark.sync
def test_hier_allreduce_parity_f32():
    ir = _assert_parity(AllReduce(bucket_bytes=1 << 20),
                        AllReduce(bucket_bytes=1 << 20, hier=True),
                        tol=1e-6)
    kinds = {l.kind for l in ir.legs}
    assert sir.LEG_DCN_ALL_REDUCE in kinds
    assert sir.LEG_HIER_ALL_GATHER in kinds


@pytest.mark.sync
def test_hier_zero1_parity_f32():
    ir = _assert_parity(Zero1(), Zero1(hier=True), tol=1e-6)
    kinds = {l.kind for l in ir.legs}
    assert sir.LEG_DCN_EXCHANGE in kinds
    # the ZeRO-1 two-tier param gather: DCN then ICI
    ag_tiers = {l.tier for l in ir.legs
                if l.kind == sir.LEG_HIER_ALL_GATHER}
    assert ag_tiers == {sir.TIER_DCN, sir.TIER_ICI}


@pytest.mark.sync
def test_hier_allreduce_parity_int8_dcn(monkeypatch):
    monkeypatch.setenv("AUTODIST_DCN_WIRE", "int8")
    ir = _assert_parity(AllReduce(bucket_bytes=1 << 20),
                        AllReduce(bucket_bytes=1 << 20, hier=True),
                        tol=2e-2)
    dcn = [l for l in ir.legs if l.kind == sir.LEG_DCN_ALL_REDUCE]
    assert dcn and all(sir.is_quantizing(l.compressor) for l in dcn)


@pytest.mark.sync
def test_hier_zero1_parity_int8_dcn(monkeypatch):
    monkeypatch.setenv("AUTODIST_DCN_WIRE", "int8")
    _assert_parity(Zero1(), Zero1(hier=True), tol=2e-2)


def _dcn_wire(monkeypatch, hier, wire):
    """(DCN-tier leg count, DCN-tier wire bytes) of the session's IR on
    the 2-slice spec."""
    from autodist_tpu.strategy.cost_model import leg_tier

    monkeypatch.setenv("AUTODIST_DCN_WIRE", wire)
    params, loss_fn, _ = _problem()
    _, sess = _session(AllReduce(bucket_bytes=1 << 20, hier=hier),
                       _spec(2), params, loss_fn)
    ir = sess.schedule_ir
    assert not sir.errors(sir.verify(ir))
    dcn = [l for l in ir.legs if leg_tier(l, ir) == sir.TIER_DCN]
    return len(dcn), sum(l.nbytes for l in dcn)


# (hier, AUTODIST_DCN_WIRE) of the mode with less DCN wire, then of the other
@pytest.mark.parametrize("smaller, larger", [
    ((True, ""), (False, "")),          # two tiers against one ring
    ((True, "int8"), (True, "")),       # the quantized DCN hop
], ids=["hier_below_flat_ring", "int8_below_f32_hier"])
def test_dcn_wire_bytes_mode_against_mode(monkeypatch, smaller, larger):
    """On two slices the flat ring's every hop is DCN-tier; the
    two-tier lowering crosses slices with one shard exchange, and
    ``AUTODIST_DCN_WIRE=int8`` shrinks that exchange again."""
    n_s, wire_s = _dcn_wire(monkeypatch, *smaller)
    n_l, wire_l = _dcn_wire(monkeypatch, *larger)
    assert n_s > 0 and n_l > 0
    assert 0 < wire_s < wire_l


def test_static_and_runtime_fingerprints_match():
    """ir_from_facts (the analysis/search side) and the runtime's
    build_schedule_ir emit the identical two-tier program."""
    from autodist_tpu.analysis.search import facts_for_candidate

    params, loss_fn, _ = _problem()
    spec = _spec(2)
    builder = AllReduce(bucket_bytes=1 << 20, hier=True)
    ad, sess = _session(builder, spec, params, loss_fn)
    runtime_ir = sess.schedule_ir
    strategy = builder.build(ad.graph_item, spec)
    facts, _, guard, prune = facts_for_candidate(
        strategy, ad.graph_item, {"data": 8}, resource_spec=spec)
    assert prune is None
    static_ir = sir.ir_from_facts(facts, axes={"data": 8}, guard=guard,
                                  num_slices=2)
    assert static_ir.fingerprint() == runtime_ir.fingerprint()


# -- ResourceSpec: slice fields + divisibility fail-fast ---------------------

def test_resource_spec_two_tier_fields():
    spec = _spec(2, dcn_gbps=50)
    assert spec.num_slices == 2
    assert spec.dcn_gbps == 50
    assert spec.dcn_bytes_per_s == 50e9 / 8
    flat = _spec(1)
    assert flat.num_slices == 1


def test_slice_mismatch_is_one_shared_rule_string():
    reason = slice_mismatch_reason(8, 3)
    assert reason is not None and reason.startswith(RULE_SLICE_MISMATCH)
    assert slice_mismatch_reason(8, 4) is None
    assert slice_mismatch_reason(8, 1) is None
    with pytest.raises(ResourceSpecError, match="legality/slice-mismatch"):
        ResourceSpec(resource_info={
            "nodes": [{"address": "localhost", "chips": 8,
                       "chief": True}],
            "num_slices": 3})


# -- beam search: the hier gene ----------------------------------------------

def _flat_cal(bandwidth=45e9, alpha=5e-6):
    from autodist_tpu.telemetry.calibration import LEG_KINDS, LegCalibration

    cal = LegCalibration()
    for kind in LEG_KINDS:
        cal.bandwidths[kind] = float(bandwidth)
        cal.alphas[kind] = alpha
    return cal


def test_beam_flips_to_hier_on_narrow_dcn():
    """Planted flat calibration, multi-slice spec with a narrow DCN:
    the flat ring books every byte at DCN speed while the hierarchy
    ships only the 1/d_in shard across — beam must pick hier.  The
    same fixture on a single-slice spec must keep flat and never set
    the gene."""
    from autodist_tpu.strategy.search import SearchSpace, beam_search

    gi = GraphItem({"w": jnp.zeros((2048, 2048), jnp.float32),
                    "b": jnp.zeros((2048,), jnp.float32)},
                   accum_steps=4)
    cal = _flat_cal()
    space = SearchSpace(max_rounds=2)
    narrow = beam_search(gi, _spec(2, dcn_gbps=10), space=space,
                         constants=cal)
    assert any(g.hier for _, g in narrow.best.genes), narrow.best.name
    single = beam_search(gi, _spec(1), space=space, constants=cal)
    assert not any(g.hier for _, g in single.best.genes)
    assert not any(g.hier for ev in single.evaluated
                   for _, g in ev.genes)


# -- the --simulate sweep ----------------------------------------------------

def _sweep_gi():
    return GraphItem({"w": jnp.zeros((1024, 1024), jnp.float32)})


def _make_strategy(gi):
    def make(spec, hier):
        return (AllReduce(hier=True) if hier else AllReduce()).build(
            gi, spec)
    return make


def test_simulate_sweep_ranks_and_prunes():
    from autodist_tpu.analysis.simulate import parse_sweep_spec, run_sweep

    gi = _sweep_gi()
    config = parse_sweep_spec("mesh=data=8;slices=2,3;dcn=10,100")
    report = run_sweep(gi, _make_strategy(gi), config)
    assert report["n_points"] == 4
    by_key = {(p["num_slices"], p["dcn_gbps"]): p
              for p in report["points"]}
    # slices=3 cannot tile 8 chips: pruned with the shared rule string
    for dcn in (10.0, 100.0):
        assert by_key[(3, dcn)]["pruned_by"].startswith(
            RULE_SLICE_MISMATCH)
    # narrow DCN favors the hierarchy; modes are priced and ranked
    narrow = by_key[(2, 10.0)]
    assert narrow["best_mode"] in ("hier", "hier_int8")
    assert set(narrow["ranking"]) == {"flat", "hier", "hier_int8"}
    flat_cell = narrow["modes"]["flat"]
    hier_cell = narrow["modes"]["hier"]
    assert hier_cell["predicted_step_s"] < flat_cell["predicted_step_s"]
    # the two-tier decomposition moves wire off the DCN
    assert hier_cell["wire_by_tier"]["dcn"] \
        < flat_cell["wire_by_tier"]["dcn"]
    # goodput rides every priced cell (the checkpoint stall dominates
    # these micro step times, so the ratio is small but well-formed)
    for cell in (flat_cell, hier_cell):
        ratio = cell["goodput"]["goodput_ratio"]
        assert ratio is not None and 0 < ratio <= 1


def test_simulate_prunes_over_hbm_point():
    from autodist_tpu.analysis.simulate import parse_sweep_spec, run_sweep

    gi = _sweep_gi()
    config = parse_sweep_spec("mesh=data=8;slices=1;dcn=25;hbm=0.0001")
    report = run_sweep(gi, _make_strategy(gi), config)
    assert report["n_over_hbm"] == 1
    (point,) = report["points"]
    assert "memory/watermark-exceeds-hbm" in point["pruned_by"]


def test_simulate_large_topology_is_fast():
    """A 1024-chip 2-level sweep point prices through the pure model in
    well under the 30 s budget (no mesh, no jax trace)."""
    import time

    from autodist_tpu.analysis.simulate import parse_sweep_spec, run_sweep

    gi = _sweep_gi()
    config = parse_sweep_spec("mesh=data=1024;slices=4;dcn=25,100")
    t0 = time.perf_counter()
    report = run_sweep(gi, _make_strategy(gi), config)
    assert time.perf_counter() - t0 < 30
    assert all("best_mode" in p for p in report["points"])


@pytest.mark.analysis
def test_simulate_cli_subprocess_exit_codes(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    base = [sys.executable, "-m", "autodist_tpu.analysis", "mlp",
            "AllReduce", "--json"]
    ok = subprocess.run(
        base + ["--simulate", "mesh=data=8;slices=1,2;dcn=25"],
        capture_output=True, env=env, cwd=REPO, timeout=300)
    assert ok.returncode == 0, ok.stderr.decode()
    report = json.loads(ok.stdout.decode())
    assert report["n_points"] == 2 and report["n_over_hbm"] == 0
    over = subprocess.run(
        base + ["--simulate",
                "mesh=data=8;slices=1;dcn=25;hbm=0.0000001"],
        capture_output=True, env=env, cwd=REPO, timeout=300)
    assert over.returncode == 1, over.stdout.decode()
    report = json.loads(over.stdout.decode())
    assert report["n_over_hbm"] == 1


# -- telemetry compare: one-sided leg kinds ----------------------------------

@pytest.mark.telemetry
def test_compare_reports_new_and_removed_leg_kinds(tmp_path, capsys):
    """Flipping a run to two-tier sync changes its leg-kind set; the
    compare report must label the one-sided kinds instead of crashing
    or silently dropping them."""
    from autodist_tpu.telemetry import profiler as prof
    from autodist_tpu.telemetry import timeline as tl
    from autodist_tpu.telemetry.__main__ import main

    def write_run(name, kinds):
        run = tmp_path / name
        run.mkdir()
        with open(run / "steps-host-1.jsonl", "w") as f:
            for i in range(4):
                rec = tl.StepRecord(step=i, time_unix=1000.0 + i * 0.01,
                                    step_time_s=0.01, host="host")
                f.write(rec.to_json() + "\n")
        prof.write_leg_samples(
            [prof.LegSample(schedule_fingerprint="fp", leg_id=f"{k}/0",
                            kind=k, measured_s=1e-3, nbytes=1 << 20,
                            time_unix=1000.0) for k in kinds], str(run))
        return run

    run_a = write_run("flat", ["all_reduce"])
    run_b = write_run("hier", [sir.LEG_HIER_REDUCE_SCATTER,
                               sir.LEG_DCN_ALL_REDUCE])
    assert main([str(run_a), "--compare", str(run_b), "--json"]) == 0
    cmp = json.loads(capsys.readouterr().out)
    assert cmp["leg_kinds"]["all_reduce"]["status"] == "removed"
    assert cmp["leg_kinds"][sir.LEG_DCN_ALL_REDUCE]["status"] == "new"
    assert main([str(run_a), "--compare", str(run_b)]) == 0
    human = capsys.readouterr().out
    assert "(new in b)" in human and "(removed in b)" in human
