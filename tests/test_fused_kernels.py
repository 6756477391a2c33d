"""Fused Pallas kernel suite vs its unfused references (docs/kernels.md).

Every kernel runs in interpret mode on the CPU mesh (the exact bodies
the TPU compiles) against the arithmetic it replaces: the guard's two
reductions, the optax Adam chain, the quantize/dequantize composition of
``quant_ring``, and the paged gather-softmax.  Plus the IR surface —
fused leg kinds, fingerprints, mutation goldens for the new
``schedule/fused-inconsistent`` rule — the calibration kinds, the shared
drop-reason rule, and a full fused-vs-unfused session parity drill under
the ``AUTODIST_FUSED_INTERPRET`` escape hatch.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.kernel.synchronization import quant_ring
from autodist_tpu.kernel.synchronization import schedule_ir as sir
from autodist_tpu.ops import fused_kernels as fk

pytestmark = pytest.mark.kernels


# ---------------------------------------------------------------------------
# kernel 1: fused detect stats
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [7, 256, 10_001, fk._BLOCK_ELEMS * 2])
def test_detect_stats_matches_reference(n):
    rng = np.random.default_rng(n)
    v = jnp.asarray(rng.standard_normal(n), jnp.float32)
    nf, sq = fk.fused_detect_stats(v)
    assert float(nf) == 0.0
    np.testing.assert_allclose(float(sq), float(jnp.sum(v * v)),
                               rtol=1e-6)


def test_detect_stats_finite_bit_bit_identical():
    """The skip decision is driven by the finite BIT; count > 0 must
    agree with ``1 - all(isfinite)`` exactly for NaN, Inf, and clean
    inputs — not just approximately."""
    rng = np.random.default_rng(0)
    base = jnp.asarray(rng.standard_normal(5000), jnp.float32)
    for poison in (None, jnp.nan, jnp.inf, -jnp.inf):
        v = base if poison is None else base.at[137].set(poison)
        nf, sq = fk.fused_detect_stats(v)
        ref_bit = bool(jnp.all(jnp.isfinite(v)))
        assert (float(nf) == 0.0) == ref_bit
        if poison is None:
            assert np.isfinite(float(sq))
        else:
            # NaN/Inf propagate into the square sum exactly as in the
            # unfused sum(v*v) — the norm is poisoned either way.
            assert not np.isfinite(float(sq))


def test_pack_detect_is_pack_plus_stats():
    from autodist_tpu.kernel.synchronization.bucketing import (
        assign_buckets, pack_bucket)
    buckets = assign_buckets(
        [("a", (32, 8), "float32", "NoneCompressor", 0, "all_reduce"),
         ("b", (100,), "float32", "NoneCompressor", 0, "all_reduce")],
        shard_divisor=8)
    (b,) = buckets
    rng = np.random.default_rng(3)
    leaves = [jnp.asarray(rng.standard_normal((32, 8)), jnp.float32),
              jnp.asarray(rng.standard_normal(100), jnp.float32)]
    vec, nf, sq = fk.fused_pack_detect(b, leaves)
    np.testing.assert_array_equal(np.asarray(vec),
                                  np.asarray(pack_bucket(b, leaves)))
    assert float(nf) == 0.0
    np.testing.assert_allclose(float(sq), float(jnp.sum(vec * vec)),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# kernel 2: fused unscale/clip/Adam update
# ---------------------------------------------------------------------------

def _optax_chain(p, g, opt, state, mult):
    scaled = jax.tree_util.tree_map(lambda x: x * mult, g)
    updates, state = opt.update(scaled, state, p)
    return optax.apply_updates(p, updates), state


@pytest.mark.parametrize("mult_val", [1.0, 0.25])
def test_fused_adam_matches_optax_chain(mult_val):
    """The PR 5 exactness contract: the fused shard update equals the
    optax chain (unscale*clip multiplier, then adam) at 1e-6 over
    multiple steps, with the shared step counter advancing."""
    spec = fk.AdamSpec(lr=1e-3)
    opt = optax.adam(spec.lr, b1=spec.b1, b2=spec.b2, eps=spec.eps)
    rng = np.random.default_rng(7)
    n = 3000
    p_ref = {"v": jnp.asarray(rng.standard_normal(n), jnp.float32)}
    state = opt.init(p_ref)
    p = p_ref["v"]
    mu = jnp.zeros(n, jnp.float32)
    nu = jnp.zeros(n, jnp.float32)
    mult = jnp.float32(mult_val)
    for step in range(3):
        g = {"v": jnp.asarray(rng.standard_normal(n), jnp.float32)}
        p_ref, state = _optax_chain(p_ref, g, opt, state, mult)
        p, mu, nu = fk.fused_adam_update(
            p, g["v"], mu, nu, jnp.int32(step), spec, mult=mult)
        np.testing.assert_allclose(np.asarray(p), np.asarray(p_ref["v"]),
                                   atol=1e-6, rtol=0,
                                   err_msg=f"step {step}")
    adam_ref = fk.find_adam_state(state)
    np.testing.assert_allclose(np.asarray(mu), np.asarray(adam_ref.mu["v"]),
                               atol=1e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(nu), np.asarray(adam_ref.nu["v"]),
                               atol=1e-6, rtol=0)


def test_fusable_adam_behaves_like_optax_adam():
    fused = fk.fusable_adam(1e-2)
    base = optax.adam(1e-2)
    p = {"w": jnp.ones((4,), jnp.float32)}
    g = {"w": jnp.full((4,), 0.5, jnp.float32)}
    u1, _ = fused.update(g, fused.init(p), p)
    u2, _ = base.update(g, base.init(p), p)
    np.testing.assert_array_equal(np.asarray(u1["w"]), np.asarray(u2["w"]))
    assert fused.fused_spec.lr == pytest.approx(1e-2)


def test_adam_state_probe_and_replace():
    opt = optax.adam(1e-3)
    state = opt.init({"x": jnp.zeros(4)})
    adam = fk.find_adam_state(state)
    assert adam is not None and hasattr(adam, "mu")
    new = fk.replace_adam_state(state, adam._replace(count=adam.count + 5))
    assert int(fk.find_adam_state(new).count) == 5
    # a non-adam chain has no addressable moments
    sgd_state = optax.sgd(0.1).init({"x": jnp.zeros(4)})
    assert fk.find_adam_state(sgd_state) is None


# ---------------------------------------------------------------------------
# kernel 3: quantize-at-the-hop
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", [quant_ring.WIRE_INT8,
                                 quant_ring.WIRE_FP8_E4M3])
def test_fused_quantize_matches_quantize_blocks(fmt):
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal(3000) * 3, jnp.float32)
    q_ref, s_ref, sat_ref = quant_ring.quantize_blocks(x, fmt)
    q, s, err, sat = fk.fused_quantize(x, fmt)
    np.testing.assert_array_equal(np.asarray(q_ref), np.asarray(q))
    np.testing.assert_allclose(np.asarray(s_ref), np.asarray(s), rtol=1e-6)
    # err is self-consistent with the kernel's own (q, scales) to
    # round-off, and within 2e-5 of the unfused composition (the scale's
    # last-bit difference between XLA and the interpreter amplifies
    # through q*scale).
    np.testing.assert_allclose(
        np.asarray(err),
        np.asarray(x - quant_ring.dequantize_blocks(q, s)),
        atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(err),
        np.asarray(x - quant_ring.dequantize_blocks(q_ref, s_ref)),
        atol=2e-5)
    # fp8 counts |y| > qmax on the unrounded y; the block's amax element
    # sits exactly AT the rail, so the scale's last bit can flip its
    # count by one between XLA and the interpreter.  Int8 rounds first
    # and is robust; non-finite saturation is pinned exactly below.
    slack = 0 if fmt.name == "int8" else 1
    assert abs(float(sat) - float(sat_ref)) <= slack
    poisoned = x.at[5].set(jnp.inf).at[900].set(jnp.nan)
    _, _, _, sat_p = fk.fused_quantize(poisoned, fmt)
    _, _, sat_p_ref = quant_ring.quantize_blocks(poisoned, fmt)
    assert float(sat_p) >= 2.0
    assert abs(float(sat_p) - float(sat_p_ref)) <= slack


def test_fused_hop_matches_composition():
    """One hop boundary fused == dequantize ∘ add ∘ requantize of the
    unfused path (wire payload bit-equal, scales/err at 1e-6)."""
    fmt = quant_ring.WIRE_INT8
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.standard_normal(2048), jnp.float32)
    chunk = jnp.asarray(rng.standard_normal(2048), jnp.float32)
    q0, s0, _ = quant_ring.quantize_blocks(x, fmt)
    acc_ref = quant_ring.dequantize_blocks(q0, s0) + chunk
    q_ref, s_ref, _ = quant_ring.quantize_blocks(acc_ref, fmt)
    q, s, err, _ = fk.fused_hop_accumulate(q0, s0, chunk, fmt)
    np.testing.assert_array_equal(np.asarray(q_ref), np.asarray(q))
    np.testing.assert_allclose(np.asarray(s_ref), np.asarray(s), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(err),
        np.asarray(acc_ref - quant_ring.dequantize_blocks(q_ref, s_ref)),
        atol=1e-6)
    acc = fk.fused_dequant_add(q0, s0, chunk, fmt)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(acc_ref),
                               atol=1e-6)


def test_fused_ring_reduce_scatter_matches_unfused():
    """The whole fused ring on a real 8-device CPU mesh: shard sums,
    error-feedback vectors, and saturation counts match the unfused
    ring at 1e-6 (the wire payloads are the same grid)."""
    from jax.sharding import Mesh, PartitionSpec as P

    n = 8
    mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
    rng = np.random.default_rng(13)
    vec = jnp.asarray(rng.standard_normal((n, 2048)), jnp.float32)

    def run(fused):
        def body(v):
            out, err, sat = quant_ring.quantized_ring_reduce_scatter(
                v.reshape(-1), "data", n, quant_ring.WIRE_INT8,
                fused=fused)
            return out, err, sat[None]
        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P("data"),
            out_specs=(P("data"), P("data"), P("data")),
            check_vma=False))
        return fn(vec)

    out_u, err_u, sat_u = run(False)
    out_f, err_f, sat_f = run(True)
    np.testing.assert_allclose(np.asarray(out_u), np.asarray(out_f),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(err_u), np.asarray(err_f),
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(sat_u), np.asarray(sat_f))


# ---------------------------------------------------------------------------
# kernel 4: paged attention
# ---------------------------------------------------------------------------

def _paged_reference(q, kc, vc, bt, rel):
    b, h, dh = q.shape
    bs = kc.shape[1]
    w = bt.shape[1] * bs
    kb = jnp.take(kc, bt, axis=0).reshape(b, w, h, dh)
    vb = jnp.take(vc, bt, axis=0).reshape(b, w, h, dh)
    logits = jnp.einsum("bhk,bwhk->bhw", q, kb.astype(q.dtype)) \
        / jnp.sqrt(jnp.asarray(dh, q.dtype))
    mask = jnp.arange(w)[None, None, :] <= rel[:, None, None]
    logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32),
                           axis=-1).astype(q.dtype)
    return jnp.einsum("bhw,bwhk->bhk", probs, vb.astype(q.dtype))


@pytest.mark.parametrize("rel_spec", ["varied", "first", "full"])
def test_paged_attention_matches_gather_reference(rel_spec):
    rng = np.random.default_rng(21)
    b, h, dh, nb, bs, maxb = 3, 2, 16, 12, 4, 5
    q = jnp.asarray(rng.standard_normal((b, h, dh)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((nb, bs, h, dh)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((nb, bs, h, dh)), jnp.float32)
    bt = jnp.asarray(rng.integers(1, nb, (b, maxb)), jnp.int32)
    rel = {"varied": jnp.asarray([0, 7, 19], jnp.int32),
           "first": jnp.zeros((b,), jnp.int32),
           "full": jnp.full((b,), maxb * bs - 1, jnp.int32)}[rel_spec]
    out = fk.paged_attention(q, kc, vc, bt, rel)
    ref = _paged_reference(q, kc, vc, bt, rel)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_paged_engine_token_exact_with_kernel(monkeypatch):
    """The serving acceptance drill: a PagedDecodeEngine decoding
    through the fused kernel is TOKEN-EXACT vs the per-request
    `generate` oracle — prefix blocks, mid-table indirection, dead
    slots and all.  The paged jit cache is cleared so the fused
    decision re-resolves for this trace."""
    from autodist_tpu.models.generate import make_generator
    from autodist_tpu.models.transformer import dense_attention
    from autodist_tpu.models.transformer_lm import transformer_lm
    from autodist_tpu.serving import PagedDecodeEngine
    from autodist_tpu.serving import paged_kv

    monkeypatch.setenv("AUTODIST_FUSED_KERNELS", "paged_attention")
    monkeypatch.setenv("AUTODIST_FUSED_INTERPRET", "1")
    paged_kv._paged_chunk_program.clear_cache()
    paged_kv._paged_prefill_program.clear_cache()
    try:
        vocab = 41
        spec = transformer_lm(vocab_size=vocab, num_layers=2, num_heads=2,
                              head_dim=8, d_ff=32, max_len=48, seq_len=16,
                              attn_fn=dense_attention)
        params = spec.init(jax.random.PRNGKey(0))
        rng = np.random.RandomState(5)
        reqs = [(rng.randint(0, vocab, p).astype(np.int32), n)
                for p, n in [(3, 5), (6, 3), (2, 6)]]
        eng = PagedDecodeEngine(spec, params, slots=2, window=32,
                                block_size=8, num_blocks=24, chunk=4)
        ids = [eng.submit(p, n) for p, n in reqs]
        results = eng.run()
        gen = make_generator(spec)
        for rid, (prompt, n) in zip(ids, reqs):
            np.testing.assert_array_equal(
                results[rid], np.asarray(gen(params, prompt[None], n))[0])
        eng.assert_no_leaks()
    finally:
        paged_kv._paged_chunk_program.clear_cache()
        paged_kv._paged_prefill_program.clear_cache()


# ---------------------------------------------------------------------------
# knobs + the shared drop-reason rule
# ---------------------------------------------------------------------------

def test_requested_kernels_parsing(monkeypatch):
    monkeypatch.delenv("AUTODIST_FUSED_KERNELS", raising=False)
    assert fk.requested_kernels() == frozenset()
    monkeypatch.setenv("AUTODIST_FUSED_KERNELS", "all")
    assert fk.requested_kernels() == frozenset(fk.ALL_KERNELS)
    monkeypatch.setenv("AUTODIST_FUSED_KERNELS", "guard, quant_hop")
    assert fk.requested_kernels() == {"guard", "quant_hop"}


def test_drop_reasons_are_shared_strings():
    # off-TPU without the escape hatch
    why = fk.fused_drop_reason("guard", on_tpu=False, interpret_ok=False)
    assert why is not None and "AUTODIST_FUSED_INTERPRET" in why
    assert fk.fused_drop_reason("guard", on_tpu=False,
                                interpret_ok=True) is None
    # update-specific gates
    assert "fusable_adam" in fk.fused_drop_reason(
        "update", on_tpu=True, optimizer_fusable=False)
    assert "ScaleByAdamState" in fk.fused_drop_reason(
        "update", on_tpu=True, adam_state_shaped=False)
    assert "float32" in fk.fused_drop_reason(
        "update", on_tpu=True, f32_buckets=False)
    assert "unknown fused kernel" in fk.fused_drop_reason(
        "nope", on_tpu=True)


def test_resolve_fused_off_tpu_drops_with_warn_reason(monkeypatch):
    monkeypatch.setenv("AUTODIST_FUSED_KERNELS", "all")
    monkeypatch.delenv("AUTODIST_FUSED_INTERPRET", raising=False)
    active, drops = fk.resolve_fused(
        guard=True, has_rs=True, has_quant_ring=True,
        optimizer_fusable=True)
    assert active == ()
    assert {k for k, _ in drops} == {"guard", "update", "quant_hop"}
    for _, why in drops:
        assert "TPU backend" in why


def test_resolve_fused_quiet_when_inapplicable(monkeypatch):
    """A requested kernel whose hot path does not exist in the program
    is silently inapplicable, not a WARN."""
    monkeypatch.setenv("AUTODIST_FUSED_KERNELS", "all")
    monkeypatch.setenv("AUTODIST_FUSED_INTERPRET", "1")
    active, drops = fk.resolve_fused(
        guard=False, has_rs=False, has_quant_ring=False)
    assert active == () and drops == []


# ---------------------------------------------------------------------------
# schedule IR: fused variants, goldens, pricing, calibration kinds
# ---------------------------------------------------------------------------

def _fused_ir(fused_kernels=("guard", "update", "quant_hop")):
    from autodist_tpu.kernel.synchronization import bucketing, overlap
    entries = [(f"l{i}/w", (256, 256), "float32", "Int8Compressor", 0,
                "reduce_scatter") for i in range(4)]
    buckets = bucketing.assign_buckets(entries, bucket_bytes=256 << 10,
                                       shard_divisor=8)
    plan = overlap.resolve_overlap(["ring"], accum_steps=1,
                                   buckets=buckets, d=8, has_rs=True)
    return sir.build_schedule_ir(axes={"data": 8}, accum_steps=1,
                                 buckets=buckets, plan=plan, guard=True,
                                 fused_kernels=fused_kernels)


def test_fused_ir_variants_verify_and_fingerprint_distinctly():
    base = _fused_ir(())
    fused = _fused_ir()
    assert not sir.errors(sir.verify(base))
    assert not sir.errors(sir.verify(fused))
    assert base.fingerprint() != fused.fingerprint()
    kinds = {l.kind for l in fused.legs}
    assert {sir.LEG_FUSED_HOP, sir.LEG_FUSED_DETECT,
            sir.LEG_FUSED_UPDATE} <= kinds
    assert all(n.get("hop_fused") for n in fused.buckets)
    # serialization round-trips the fused record + fingerprint
    rt = sir.ScheduleIR.from_json(fused.to_json())
    assert rt.fused_kernels == ("guard", "update", "quant_hop")
    assert rt.fingerprint() == fused.fingerprint()
    # an empty fused record serializes exactly as before (stable
    # fingerprints for every pre-fusion program)
    assert "fused_kernels" not in base.to_dict()


def test_golden_fused_legs_without_record_rejected():
    fused = _fused_ir()
    mutated = sir.ScheduleIR.from_json(fused.to_json())
    mutated.fused_kernels = ()
    rules = {v.rule for v in sir.errors(sir.verify(mutated))}
    assert rules == {sir.RULE_FUSED_INCONSISTENT}


def test_golden_fused_hop_for_linear_compressor_rejected():
    fused = _fused_ir()
    mutated = sir.ScheduleIR.from_json(fused.to_json())
    mutated.legs = [
        l if l.kind != sir.LEG_FUSED_HOP else
        sir.Leg(**{**{f: getattr(l, f)
                      for f in sir.Leg.__dataclass_fields__},
                   "compressor": "NoneCompressor"})
        for l in mutated.legs]
    rules = {v.rule for v in sir.errors(sir.verify(mutated))}
    assert sir.RULE_FUSED_INCONSISTENT in rules


def test_golden_fused_hop_order_still_ring_checked():
    """The ring grammar covers fused hops too: swapping two fused hops
    of one chain deadlocks the ppermute and must be rejected by the
    established ring-hop-order rule."""
    fused = _fused_ir()
    mutated = sir.ScheduleIR.from_json(fused.to_json())
    hops = [l for l in mutated.legs if l.kind == sir.LEG_FUSED_HOP]
    chain = hops[0].chain
    chain_hops = [l for l in hops if l.chain == chain]
    assert len(chain_hops) >= 2
    a, b = chain_hops[0], chain_hops[1]

    def swap(l):
        if l.id == a.id:
            return sir.Leg(**{**{f: getattr(a, f)
                                 for f in sir.Leg.__dataclass_fields__},
                              "hop": b.hop})
        if l.id == b.id:
            return sir.Leg(**{**{f: getattr(b, f)
                                 for f in sir.Leg.__dataclass_fields__},
                              "hop": a.hop})
        return l
    mutated.legs = [swap(l) for l in mutated.legs]
    rules = {v.rule for v in sir.errors(sir.verify(mutated))}
    assert sir.RULE_RING_HOP_ORDER in rules


def test_estimate_ir_cost_prices_fused_kinds():
    from autodist_tpu.strategy.cost_model import estimate_ir_cost
    from autodist_tpu.telemetry.calibration import fit_leg_constants

    fused = _fused_ir()
    # uncalibrated: fused wire still counted (fused_hop is a collective)
    rep = estimate_ir_cost(fused)
    assert rep.wire_bytes > 0 and rep.time_s > 0
    samples = [
        dict(kind="fused_hop", measured_s=2e-4, nbytes=40_000,
             compressor="Int8Compressor"),
        dict(kind="fused_detect", measured_s=6e-5, nbytes=262_144,
             compressor="NoneCompressor"),
        dict(kind="fused_update", measured_s=4e-5, nbytes=32_768,
             compressor="NoneCompressor"),
        dict(kind="ppermute_hop", measured_s=3e-4, nbytes=40_000,
             compressor="NoneCompressor"),
    ]
    cal = fit_leg_constants(samples)
    assert {"fused_hop", "fused_detect", "fused_update"} \
        <= set(cal.bandwidths)
    rep_cal = estimate_ir_cost(fused, constants=cal)
    assert rep_cal.time_s > 0
    # fused-vs-unfused price differently once both kinds are fitted
    unfused = _fused_ir(())
    assert estimate_ir_cost(unfused, constants=cal).time_s \
        != pytest.approx(rep_cal.time_s)


def test_profiler_micro_runs_cover_fused_kinds():
    from autodist_tpu.telemetry.profiler import LegProfiler, span_leg_kind

    prof = LegProfiler(warmup=0, repeats=1)
    samples = prof.profile_ir(_fused_ir())
    kinds = {s.kind for s in samples}
    assert {"fused_hop", "fused_detect", "fused_update"} <= kinds
    # the span vocabulary maps the fused sync scopes
    assert span_leg_kind("autodist_sync/quant_ring_fused/leg2") \
        == "fused_hop"
    assert span_leg_kind("autodist_sync/fused_pack_detect/b0") \
        == "fused_detect"
    assert span_leg_kind("autodist_sync/fused_shard_update") \
        == "fused_update"


# ---------------------------------------------------------------------------
# runtime + analysis fallback surfaces
# ---------------------------------------------------------------------------

def _small_session(monkeypatch, kernels, interpret):
    from autodist_tpu.autodist import AutoDist, \
        _reset_default_autodist_for_testing
    from autodist_tpu.strategy import Zero1

    if kernels:
        monkeypatch.setenv("AUTODIST_FUSED_KERNELS", kernels)
    else:
        monkeypatch.delenv("AUTODIST_FUSED_KERNELS", raising=False)
    if interpret:
        monkeypatch.setenv("AUTODIST_FUSED_INTERPRET", "1")
    else:
        monkeypatch.delenv("AUTODIST_FUSED_INTERPRET", raising=False)
    _reset_default_autodist_for_testing()
    rng = np.random.RandomState(0)
    params = {f"l{i}": {"w": jnp.asarray(rng.randn(288, 288) * 0.05,
                                         jnp.float32)} for i in range(2)}
    batch = {"x": rng.randn(16, 288).astype(np.float32),
             "y": rng.randn(16, 288).astype(np.float32)}

    def loss_fn(p, b):
        h = b["x"]
        for i in range(2):
            h = jnp.tanh(h @ p[f"l{i}"]["w"])
        return jnp.mean((h - b["y"]) ** 2)

    ad = AutoDist(strategy_builder=Zero1(bucket_bytes=1 << 20,
                                         compressor="Int8Compressor",
                                         overlap="ring"))
    with ad.scope():
        ad.capture(params=params, optimizer=fk.fusable_adam(1e-3),
                   loss_fn=loss_fn,
                   numerics={"clip_norm": 1.0, "loss_scale": None})
    sess = ad.create_distributed_session()
    return ad, sess, batch


@pytest.mark.slow
def test_session_fused_matches_unfused(monkeypatch):
    """All three training kernels active (interpret escape hatch) vs
    the unfused session: same losses, params within 1e-5 after 3 steps,
    fused record + leg kinds in the IR."""
    from autodist_tpu.autodist import _reset_default_autodist_for_testing

    def run(kernels):
        ad, sess, batch = _small_session(monkeypatch, kernels, True)
        ir = sess.schedule_ir
        placed = sess.place_batch(batch)
        losses = [float(sess.run(placed)["loss"]) for _ in range(3)]
        p = jax.tree_util.tree_map(np.asarray, sess.params)
        _reset_default_autodist_for_testing()
        return ir, losses, p

    ir_u, loss_u, p_u = run("")
    ir_f, loss_f, p_f = run("guard,update,quant_hop")
    assert ir_u.fused_kernels == ()
    assert ir_f.fused_kernels == ("guard", "update", "quant_hop")
    kinds = {l.kind for l in ir_f.legs}
    assert {sir.LEG_FUSED_HOP, sir.LEG_FUSED_DETECT,
            sir.LEG_FUSED_UPDATE} <= kinds
    np.testing.assert_allclose(loss_u, loss_f, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p_u),
                    jax.tree_util.tree_leaves(p_f)):
        np.testing.assert_allclose(a, b, atol=1e-5)


class _LogGrabber(__import__("logging").Handler):
    """The autodist logger does not propagate (its own handlers), so
    fallback-WARN assertions attach a handler directly — the
    test_quant_ring counter idiom."""

    def __init__(self):
        super().__init__()
        self.messages = []

    def emit(self, record):
        self.messages.append(record.getMessage())


def test_runtime_falls_back_off_tpu_with_warn(monkeypatch):
    """Requested kernels off-TPU (no escape hatch): the session builds
    UNFUSED, logs the shared drop reason once per kernel, and the IR
    records no fused kernels."""
    import logging

    from autodist_tpu.autodist import _reset_default_autodist_for_testing

    grab = _LogGrabber()
    logger = logging.getLogger("autodist_tpu")
    logger.addHandler(grab)
    try:
        ad, sess, batch = _small_session(monkeypatch, "all", False)
        assert sess.schedule_ir.fused_kernels == ()
        assert not any(l.kind in (sir.LEG_FUSED_HOP, sir.LEG_FUSED_DETECT,
                                  sir.LEG_FUSED_UPDATE)
                       for l in sess.schedule_ir.legs)
        msgs = [m for m in grab.messages
                if "falls back to the unfused lowering" in m]
        assert len(msgs) == 3
        assert all("TPU backend" in m for m in msgs)
    finally:
        logger.removeHandler(grab)
        _reset_default_autodist_for_testing()


def test_analysis_surfaces_fused_fallback_warn(monkeypatch):
    """The analysis schedule pass emits schedule/fused-fallback with
    the runtime's exact drop-reason string."""
    from autodist_tpu.autodist import _reset_default_autodist_for_testing
    from autodist_tpu.analysis import analyze

    ad, sess, batch = _small_session(monkeypatch, "all", False)
    try:
        report = analyze(ad.build_strategy(), ad.graph_item,
                         mesh={"data": 8})
        diags = [d for d in report.diagnostics
                 if d.rule == "schedule/fused-fallback"]
        assert diags, [d.rule for d in report.diagnostics]
        assert any("TPU backend" in d.message for d in diags)
    finally:
        _reset_default_autodist_for_testing()


def test_analysis_quiet_when_kernels_active(monkeypatch):
    """With kernels active (escape hatch) the analysis side resolves
    the SAME fused set as the runtime: no fallback WARN, and the
    runtime IR records the kernels."""
    from autodist_tpu.autodist import _reset_default_autodist_for_testing
    from autodist_tpu.analysis import analyze

    ad, sess, batch = _small_session(monkeypatch, "guard,update,quant_hop",
                                     True)
    try:
        report = analyze(ad.build_strategy(), ad.graph_item,
                         mesh={"data": 8})
        assert not [d for d in report.diagnostics
                    if d.rule == "schedule/fused-fallback"]
        assert sess.schedule_ir.fused_kernels \
            == ("guard", "update", "quant_hop")
    finally:
        _reset_default_autodist_for_testing()


def test_paged_drop_reason_warns_once_off_tpu(monkeypatch):
    import logging

    from autodist_tpu.serving import paged_kv

    monkeypatch.setenv("AUTODIST_FUSED_KERNELS", "paged_attention")
    monkeypatch.delenv("AUTODIST_FUSED_INTERPRET", raising=False)
    monkeypatch.setattr(paged_kv, "_paged_kernel_warned", False)
    grab = _LogGrabber()
    logger = logging.getLogger("autodist_tpu")
    logger.addHandler(grab)
    try:
        assert paged_kv._use_fused_paged_attention() is False
        assert paged_kv._use_fused_paged_attention() is False
    finally:
        logger.removeHandler(grab)
    msgs = [m for m in grab.messages
            if "fused kernel paged_attention falls back" in m]
    assert len(msgs) == 1 and "TPU backend" in msgs[0]
    # On a TPU the same drop is an error, not a quiet unfused run.
    monkeypatch.setattr(fk, "_platform_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="cannot lower: no Adam"):
        fk.drop_or_raise("explicit sync path", fk.KERNEL_UPDATE, "no Adam")


# ---------------------------------------------------------------------------
# shared helpers (pallas_utils + quant_scale satellites)
# ---------------------------------------------------------------------------

def test_flash_attention_uses_shared_tiling_policy():
    fa = importlib.import_module("autodist_tpu.ops.flash_attention")
    from autodist_tpu.ops import pallas_utils
    assert fa._pad_len is pallas_utils.pad_len
    assert fa._pick_block is pallas_utils.pick_block
    assert fa._use_interpret is pallas_utils.use_interpret


def test_shared_scale_rule_matches_both_quantizers():
    from autodist_tpu.ops import quant_scale
    from autodist_tpu.ops.quant import quantize_weight

    amax = jnp.asarray([0.0, 1.0, 254.0], jnp.float32)
    np.testing.assert_allclose(
        np.asarray(quant_scale.chunk_scale(amax, 127.0)),
        [1e-30, 1.0 / 127.0, 2.0])
    np.testing.assert_allclose(
        np.asarray(quant_scale.channel_scale(amax, 127.0)),
        [1.0, 1.0 / 127.0, 2.0])
    # the weight quantizer preserves its historical zero-column rule
    w = jnp.zeros((4, 2), jnp.float32).at[:, 1].set(
        jnp.asarray([1.0, -2.0, 0.5, 2.0]))
    qw = quantize_weight(w)
    np.testing.assert_allclose(np.asarray(qw.scale)[0], [1.0, 2.0 / 127.0])
    assert np.all(np.asarray(qw.q)[:, 0] == 0)
