"""Overlap-aware sync scheduler (kernel/synchronization/overlap.py).

The contracts of the PR issue: (1) pipelined accumulation is
numerically equivalent (1e-6) to the sequential loop on the CPU mesh
across sync modes × compressors — including uneven tail microbatches
and the single-microbatch degenerate case; (2) ring decomposition
lowers large buckets to explicit ppermute steps (and one-shot below the
threshold) with identical numerics; (3) the ZeRO-1 param all-gather
issues in reverse bucket order; (4) the analysis rules
(sync/ring-degenerate ERROR, sync/overlap-fallback WARN) share their
reason strings with the runtime; (5) sync state is only donated when
every entry is rewritten each step.
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from autodist_tpu.autodist import AutoDist, _reset_default_autodist_for_testing
from autodist_tpu.kernel.synchronization import overlap as ov
from autodist_tpu.kernel.synchronization.bucketing import assign_buckets
from autodist_tpu.strategy import AllReduce, Zero1

pytestmark = [pytest.mark.sync, pytest.mark.overlap]


@pytest.fixture(autouse=True)
def _reset():
    _reset_default_autodist_for_testing()


# -- ring / one-shot collective lowerings ------------------------------------

def _data_mesh():
    n = jax.device_count()
    return Mesh(np.array(jax.devices()).reshape(n), ("data",)), n


def test_ring_legs_match_lax_collectives():
    """ring RS == psum_scatter, ring AG == all_gather(tiled), ring AR ==
    pmean, one-shot == pmean — same math, schedulable legs."""
    mesh, n = _data_mesh()
    x = np.random.RandomState(0).randn(n * 40).astype(np.float32)

    def f(xs):
        rs_ref = lax.psum_scatter(xs, "data", scatter_dimension=0,
                                  tiled=True)
        return (ov.ring_reduce_scatter(xs, "data", n), rs_ref,
                ov.ring_all_gather(rs_ref, "data", n),
                lax.all_gather(rs_ref, "data", axis=0, tiled=True),
                ov.ring_all_reduce_mean(xs, "data", n),
                ov.one_shot_all_reduce_mean(xs, "data", n),
                lax.pmean(xs, "data"))

    m = jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                         out_specs=(P("data"),) * 7, check_vma=False)
    rs, rs_ref, ag, ag_ref, ar, os_, ar_ref = jax.jit(m)(x)
    np.testing.assert_allclose(np.asarray(rs), np.asarray(rs_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ag), np.asarray(ag_ref))
    np.testing.assert_allclose(np.asarray(ar), np.asarray(ar_ref),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(os_), np.asarray(ar_ref),
                               rtol=1e-6, atol=1e-7)


def test_ring_degenerate_single_device_is_identity():
    x = jnp.arange(8.0)
    assert ov.ring_reduce_scatter(x, "data", 1) is x
    assert ov.ring_all_gather(x, "data", 1) is x
    assert ov.ring_all_reduce_mean(x, "data", 1) is x


# -- schedule resolution (pure rules) ----------------------------------------

def _bucket(dtype="float32", comp="NoneCompressor", nbytes=1024,
            mode="all_reduce"):
    n = max(nbytes // np.dtype(dtype).itemsize, 1)
    (b,) = assign_buckets([("v", (n,), dtype, comp, 0, mode)])
    return b


def test_resolve_none_wins_over_everything():
    plan = ov.resolve_overlap(["full", "none", "ring"], accum_steps=4,
                              buckets=[_bucket()], d=8, has_rs=True)
    assert plan.mode == "none"
    assert not (plan.pipeline or plan.ring or plan.prefetch
                or plan.one_shot_small)


def test_auto_pipelines_only_f32_uncompressed_buckets():
    f32 = _bucket("float32")
    bf16 = _bucket("bfloat16")
    comp = _bucket(comp="HorovodCompressorEF")
    plan = ov.resolve_overlap(["auto"], accum_steps=4,
                              buckets=[f32, bf16, comp], d=8, has_rs=False)
    assert plan.pipeline
    assert ov.pipeline_eligible(f32, plan.mode, 4)
    assert not ov.pipeline_eligible(bf16, plan.mode, 4)
    assert not ov.pipeline_eligible(comp, plan.mode, 4)
    # the blocked buckets carry shared-rule drop reasons
    dropped = dict(plan.drops)
    assert bf16.key in dropped and "low-precision rounding" in \
        dropped[bf16.key]
    assert comp.key in dropped and "quantizes once per bucket" in \
        dropped[comp.key]
    # explicit pipeline forces the bf16 bucket in
    assert ov.pipeline_eligible(bf16, "pipeline", 4)


def test_pipeline_degenerate_single_microbatch_falls_back():
    plan = ov.resolve_overlap(["pipeline"], accum_steps=1,
                              buckets=[_bucket()], d=8, has_rs=False)
    assert not plan.pipeline
    assert any("no microbatch loop" in why for _, why in plan.drops)


def test_auto_with_no_accum_is_quiet():
    plan = ov.resolve_overlap(["auto"], accum_steps=1,
                              buckets=[_bucket()], d=8, has_rs=False)
    assert not plan.pipeline and not plan.drops


def test_gather_schedule_reverses_bucket_order():
    bs = assign_buckets(
        [(f"v{i}", (64,), "float32", "NoneCompressor", i, "reduce_scatter")
         for i in range(3)])
    assert [b.order for b in bs] == [0, 1, 2]
    assert [b.order for b in ov.gather_schedule(bs, True)] == [2, 1, 0]
    assert [b.order for b in ov.gather_schedule(bs, False)] == [0, 1, 2]


def test_microbatch_slices():
    assert ov.microbatch_slices(8, 4) == [(0, 2), (2, 2), (4, 2), (6, 2)]
    assert ov.microbatch_slices(7, 3) == [(0, 3), (3, 2), (5, 2)]
    assert ov.microbatch_slices(4, 3) == [(0, 2), (2, 1), (3, 1)]
    with pytest.raises(ValueError, match="exceeds"):
        ov.microbatch_slices(2, 3)


# -- pipelined accumulation: numerical equivalence ---------------------------

def _problem(rows=32, seed=0):
    rng = np.random.RandomState(seed)
    params = {
        "l1": {"w": jnp.asarray(rng.randn(24, 32) * 0.1, jnp.float32),
               "b": jnp.zeros(32, jnp.float32)},
        "l2": {"w": jnp.asarray(rng.randn(32, 4) * 0.1, jnp.float32)},
    }
    batch = {"x": rng.randn(rows, 24).astype(np.float32),
             "y": rng.randn(rows, 4).astype(np.float32)}

    def loss_fn(p, b):
        h = jnp.tanh(b["x"] @ p["l1"]["w"] + p["l1"]["b"])
        return jnp.mean((h @ p["l2"]["w"] - b["y"]) ** 2)

    return params, loss_fn, batch


def _session(builder, params, loss_fn, accum=1, opt=None):
    _reset_default_autodist_for_testing()
    ad = AutoDist(strategy_builder=builder)
    with ad.scope():
        ad.capture(params=params, optimizer=opt or optax.adam(1e-2),
                   loss_fn=loss_fn, accum_steps=accum)
    return ad.create_distributed_session()


def _assert_same_trajectory(a, b, batch, steps=6, rtol=1e-6, atol=1e-7):
    for _ in range(steps):
        la, lb = a.run(batch)["loss"], b.run(batch)["loss"]
        np.testing.assert_allclose(float(la), float(lb), rtol=rtol,
                                   atol=atol)
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32),
            rtol=rtol, atol=atol),
        a.params, b.params)


@pytest.mark.parametrize("mk", [
    lambda o: AllReduce(bucket_bytes=1 << 20, overlap=o),
    lambda o: Zero1(overlap=o),
], ids=["all_reduce", "reduce_scatter"])
def test_pipelined_matches_sequential_loop(mk):
    """The acceptance contract: with accumulation active, the pipelined
    schedule (per-microbatch bucket collectives overlapping backward)
    reproduces the sequential accumulate-then-reduce loop to 1e-6 on
    both sync modes."""
    params, loss_fn, batch = _problem()
    pipelined = _session(mk("auto"), params, loss_fn, accum=4)
    sequential = _session(mk("none"), params, loss_fn, accum=4)
    _assert_same_trajectory(pipelined, sequential, batch)


@pytest.mark.parametrize("compressor", [
    "HorovodCompressor", "HorovodCompressorEF", "Int8Compressor",
    "PowerSGDCompressor"])
def test_compressed_modes_fall_back_and_stay_exact(compressor):
    """Quantizing compressors keep the one-compressed-collective-per-
    bucket-per-step contract: overlap='auto' falls back to the
    sequential loop, so the trajectory is IDENTICAL to overlap='none'
    (not merely close) for every compressor."""
    params, loss_fn, batch = _problem()
    auto = _session(AllReduce(compressor=compressor, bucket_bytes=1 << 20,
                              overlap="auto"), params, loss_fn, accum=2)
    off = _session(AllReduce(compressor=compressor, bucket_bytes=1 << 20,
                             overlap="none"), params, loss_fn, accum=2)
    _assert_same_trajectory(auto, off, batch, steps=4)


def _tail_atol(rows=32, steps=6, lr=1e-2, kappa=8.0):
    """Parameter tolerance for the uneven-tail comparisons, from float32
    arithmetic rather than by trial.

    Both schedules add the same ``rows`` row-weighted per-row gradient
    terms into every gradient element (the weights 2/4, 1/4, 1/4 and
    1/8 are powers of two, so the products are exact) but in another
    order: the pipelined schedule reduces over devices inside each
    microbatch and then adds the microbatches, the sequential loop adds
    the microbatches first.  Reassociating an n-term float32 sum moves
    it by at most ``(n-1) * u * sum|t|`` with ``u = 2**-24`` (Higham,
    *Accuracy and Stability of Numerical Algorithms*, eq. 4.4): a
    relative ``(n-1) * u * kappa``, ``kappa = sum|t| / |sum t|``.
    Measured on this problem, the two schedules' gradients differ by at
    most 0.33 of that bound, so the difference IS reassociation.  Adam's
    step ``lr * m / (sqrt(v) + eps)`` is scale-free, so the relative
    error reaches the parameter as ``lr * (n-1) * u * kappa`` per step.
    ``kappa``: the median over this problem's gradient elements is 6 to
    11; 8 is allowed.  The even-split tests pass at the default 1e-7
    and keep it."""
    return steps * lr * (rows - 1) * 2.0 ** -24 * kappa


def test_pipelined_uneven_tail_microbatches():
    """32-row global batch over 8 devices = 4 local rows; accum_steps=3
    runs uneven [2, 1, 1] microbatches, row-weighted in both the
    pipelined (unrolled) and sequential schedules."""
    params, loss_fn, batch = _problem(rows=32)
    pipelined = _session(AllReduce(bucket_bytes=1 << 20, overlap="auto"),
                         params, loss_fn, accum=3)
    sequential = _session(AllReduce(bucket_bytes=1 << 20, overlap="none"),
                          params, loss_fn, accum=3)
    _assert_same_trajectory(pipelined, sequential, batch, atol=_tail_atol())
    # ...and both match the unaccumulated full-batch step (row-mean loss)
    plain = _session(AllReduce(bucket_bytes=1 << 20), params, loss_fn)
    pipelined2 = _session(AllReduce(bucket_bytes=1 << 20, overlap="auto"),
                          params, loss_fn, accum=3)
    _assert_same_trajectory(pipelined2, plain, batch, rtol=1e-5, atol=1e-6)


def test_pipelined_zero1_uneven_tail():
    params, loss_fn, batch = _problem(rows=32)
    pipelined = _session(Zero1(overlap="auto"), params, loss_fn, accum=3)
    sequential = _session(Zero1(overlap="none"), params, loss_fn, accum=3)
    _assert_same_trajectory(pipelined, sequential, batch, atol=_tail_atol())


def test_single_microbatch_degenerate_case():
    """overlap='pipeline' with accum_steps=1 falls back (nothing to
    pipeline) and matches the plain step exactly."""
    params, loss_fn, batch = _problem()
    forced = _session(AllReduce(bucket_bytes=1 << 20, overlap="pipeline"),
                      params, loss_fn, accum=1)
    plain = _session(AllReduce(bucket_bytes=1 << 20, overlap="none"),
                     params, loss_fn, accum=1)
    _assert_same_trajectory(forced, plain, batch)


def test_explicit_pipeline_forces_bf16_bucket():
    """auto skips bf16 buckets (extra per-microbatch rounding); an
    explicit overlap='pipeline' pipelines them too, tracking the
    sequential loop at bf16 summation-order tolerance."""
    rng = np.random.RandomState(7)
    params = {"w16": jnp.asarray(rng.randn(16, 8) * 0.1, jnp.bfloat16),
              "w32": jnp.asarray(rng.randn(8, 4) * 0.1, jnp.float32)}
    batch = {"x": rng.randn(16, 16).astype(np.float32),
             "y": rng.randn(16, 4).astype(np.float32)}

    def loss_fn(p, b):
        h = jnp.tanh(b["x"] @ p["w16"].astype(jnp.float32))
        return jnp.mean((h @ p["w32"] - b["y"]) ** 2)

    forced = _session(AllReduce(bucket_bytes=1 << 20, overlap="pipeline"),
                      params, loss_fn, accum=2)
    seq = _session(AllReduce(bucket_bytes=1 << 20, overlap="none"),
                   params, loss_fn, accum=2)
    for _ in range(4):
        np.testing.assert_allclose(float(forced.run(batch)["loss"]),
                                   float(seq.run(batch)["loss"]),
                                   rtol=5e-3)


def test_pipelined_aux_keeps_stacked_contract():
    """has_aux under the pipelined schedule: aux comes back stacked on a
    leading [accum] axis, same as the sequential loop."""
    params, loss_fn, batch = _problem()

    def loss_aux(p, b):
        loss = loss_fn(p, b)
        return loss, {"l2": loss * 2}

    def make(overlap):
        _reset_default_autodist_for_testing()
        ad = AutoDist(strategy_builder=AllReduce(bucket_bytes=1 << 20,
                                                 overlap=overlap))
        with ad.scope():
            ad.capture(params=params, optimizer=optax.adam(1e-2),
                       loss_fn=loss_aux, has_aux=True, accum_steps=4)
        return ad.create_distributed_session()

    piped, seq = make("auto"), make("none")
    op, os_ = piped.run(batch), seq.run(batch)
    assert np.shape(op["aux"]["l2"]) == np.shape(os_["aux"]["l2"])
    np.testing.assert_allclose(np.asarray(op["aux"]["l2"]),
                               np.asarray(os_["aux"]["l2"]), rtol=1e-6)
    np.testing.assert_allclose(float(op["loss"]), float(os_["loss"]),
                               rtol=1e-6)


# -- ring decomposition in the lowered program -------------------------------

def _hlo(sess, batch):
    b = sess.place_batch(batch)
    return sess._step.step_fn.lower(sess.sharded_params, sess.opt_state,
                                    sess.sync_state, b).as_text()


def test_large_bucket_ring_decomposes_to_ppermute():
    """A >=256 KiB bucket under overlap='ring' lowers to explicit
    collective_permute ring steps instead of one monolithic all-reduce;
    numerics match the fused collective."""
    rng = np.random.RandomState(1)
    params = {"big": jnp.asarray(rng.randn(512, 256) * 0.02, jnp.float32)}
    batch = {"x": rng.randn(16, 512).astype(np.float32)}

    def loss_fn(p, b):
        return jnp.mean((b["x"] @ p["big"]) ** 2)

    ring = _session(AllReduce(bucket_bytes=1 << 20, overlap="ring"),
                    params, loss_fn)
    fused = _session(AllReduce(bucket_bytes=1 << 20, overlap="none"),
                     params, loss_fn)
    txt = _hlo(ring, batch)
    assert "stablehlo.collective_permute" in txt
    # ring summation order differs from the fused psum's reduction tree;
    # a few ULPs per step compound through Adam, hence the atol.
    _assert_same_trajectory(ring, fused, batch, steps=4, rtol=1e-3,
                            atol=1e-5)


def test_small_bucket_one_shot_under_explicit_ring():
    """Below the threshold, explicit ring mode picks the one-shot
    gather-and-reduce: the gradient program carries an all_gather where
    'none' carries an all_reduce."""
    params, loss_fn, batch = _problem()
    one_shot = _session(AllReduce(bucket_bytes=1 << 20, overlap="ring"),
                        params, loss_fn)
    fused = _session(AllReduce(bucket_bytes=1 << 20, overlap="none"),
                     params, loss_fn)
    assert "stablehlo.collective_permute" not in _hlo(one_shot, batch)
    assert _hlo(one_shot, batch).count("stablehlo.all_gather") > \
        _hlo(fused, batch).count("stablehlo.all_gather")
    _assert_same_trajectory(one_shot, fused, batch, steps=4)


def test_overlap_knob_routes_explicit_path():
    from autodist_tpu.kernel.synchronization import explicit_sync

    params, loss_fn, batch = _problem()
    sess = _session(AllReduce(overlap="ring"), params, loss_fn)
    assert explicit_sync.uses_explicit_path(sess._step.compiled_strategy)


# -- ZeRO-1 prefetch ---------------------------------------------------------

def test_zero1_full_overlap_matches_reference():
    """overlap='full' (pipeline + ring/one-shot + reverse-order gather)
    still reproduces the plain AllReduce trajectory at 1e-6."""
    params, loss_fn, batch = _problem()
    z = _session(Zero1(overlap="full"), params, loss_fn, accum=2)
    ref = _session(AllReduce(overlap="none"), params, loss_fn, accum=2)
    _assert_same_trajectory(z, ref, batch)


# -- donation audit ----------------------------------------------------------

def test_fallback_sync_state_is_not_donated():
    """A per-variable fallback entry (PowerSGD) can pass through a step
    untouched, so the step must NOT donate sync_state: a reference taken
    before the step (checkpoint saver pattern) stays readable."""
    params, loss_fn, batch = _problem()
    sess = _session(AllReduce(compressor="PowerSGDCompressor"),
                    params, loss_fn, opt=optax.sgd(0.1))
    before = sess.sync_state
    assert before  # PowerSGD carries per-var state
    sess.run(batch)
    sess.run(batch)
    for leaf in jax.tree_util.tree_leaves(before):
        np.asarray(leaf)  # would raise RuntimeError if donated


def test_bucket_only_sync_state_still_donated():
    """Bucket residuals are rewritten unconditionally every step, so the
    all-bucket program keeps the donation (old references are consumed —
    the memory win of donating the residual buffers)."""
    params, loss_fn, batch = _problem()
    sess = _session(AllReduce(compressor="HorovodCompressorEF",
                              bucket_bytes=1 << 20), params, loss_fn)
    before = sess.sync_state
    assert before and all(":" in k for k in before)  # bucket-keyed
    sess.run(batch)
    leaf = jax.tree_util.tree_leaves(before)[0]
    assert leaf.is_deleted()


# -- analysis rules ----------------------------------------------------------

def test_ring_degenerate_axis_is_error():
    from autodist_tpu.analysis import analyze
    from autodist_tpu.graph_item import GraphItem

    gi = GraphItem({"w": jnp.zeros((64, 64), jnp.float32)})
    report = analyze(AllReduce(overlap="ring").build(gi, _spec(1)), gi,
                     mesh={"data": 1})
    errs = report.by_rule("sync/ring-degenerate")
    assert errs and "no ring to permute over" in errs[0].message
    # legal on a real data axis
    ok = analyze(AllReduce(overlap="ring").build(gi, _spec(8)), gi,
                 mesh={"data": 8})
    assert not ok.by_rule("sync/ring-degenerate")


def test_overlap_fallback_warn_shares_runtime_reason():
    """The sync/overlap-fallback WARN carries the exact string
    overlap_drop_reason produces — one rule, lint and runtime."""
    from autodist_tpu.analysis import analyze
    from autodist_tpu.graph_item import GraphItem

    gi = GraphItem({"w": jnp.zeros((64, 64), jnp.float32)})
    report = analyze(
        Zero1(compressor="PowerSGDCompressor").build(gi, _spec(8)),
        gi, mesh={"data": 8})
    warns = report.by_rule("sync/overlap-fallback")
    assert warns
    expected = ov.overlap_drop_reason(
        "auto", accum_steps=1, compressor="PowerSGDCompressor",
        bucketable=False, explicit_path=True)
    assert expected in warns[0].message


def test_overlap_unknown_mode_is_error():
    from autodist_tpu.analysis import analyze
    from autodist_tpu.graph_item import GraphItem
    from autodist_tpu.strategy.base import (
        AllReduceSynchronizerConfig,
        Strategy,
        VarConfig,
    )

    gi = GraphItem({"w": jnp.zeros((8, 8), jnp.float32)})
    s = Strategy(node_config=[VarConfig(
        "w", synchronizer=AllReduceSynchronizerConfig(overlap="warp"))])
    report = analyze(s, gi, mesh={"data": 8})
    assert report.by_rule("sync/overlap-unknown")


def test_builder_rejects_unknown_overlap():
    with pytest.raises(ValueError, match="overlap"):
        AllReduce(overlap="warp")
    with pytest.raises(ValueError, match="overlap"):
        Zero1(overlap="warp")


def test_overlap_round_trips_through_ir():
    from autodist_tpu.graph_item import GraphItem
    from autodist_tpu.strategy.base import Strategy

    gi = GraphItem({"w": jnp.zeros((8, 8), jnp.float32)})
    s = Zero1(overlap="full").build(gi, _spec(8))
    s.serialize()
    s2 = Strategy.deserialize(s.id)
    assert s2.node_config[0].synchronizer.overlap == "full"


def test_analysis_cli_flags_illegal_ring_request():
    """Acceptance: the CLI exits nonzero on a ring request over a
    size-1 data axis."""
    proc = subprocess.run(
        [sys.executable, "-m", "autodist_tpu.analysis", "mlp", "Zero1",
         "--mesh", "data=1", "--overlap", "ring"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "sync/ring-degenerate" in proc.stdout
    ok = subprocess.run(
        [sys.executable, "-m", "autodist_tpu.analysis", "mlp", "Zero1",
         "--mesh", "data=8", "--overlap", "full"],
        capture_output=True, text=True, timeout=120)
    assert ok.returncode == 0, ok.stdout + ok.stderr


def _spec(chips):
    from autodist_tpu.resource_spec import ResourceSpec

    return ResourceSpec(resource_info={
        "nodes": [{"address": "a", "chips": chips, "chief": True}]})
