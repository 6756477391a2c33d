"""Continuous-batching DecodeEngine vs per-request `generate` (oracle).

The engine's claim is token-exactness: slot-based continuous batching
with a uniform cache tick and per-slot offset masks must reproduce the
single-request KV-cache decode bit-for-bit (greedy).  Plus scheduler
behavior: slot reuse, early-eos harvest, ring wrap, utilization
accounting, and validation errors.
"""
import jax
import numpy as np
import pytest

from autodist_tpu.models.generate import make_generator
from autodist_tpu.models.transformer import dense_attention
from autodist_tpu.models.transformer_lm import transformer_lm
from autodist_tpu.serving import DecodeEngine

VOCAB = 61


@pytest.fixture(scope="module")
def lm():
    spec = transformer_lm(vocab_size=VOCAB, num_layers=2, num_heads=2,
                          head_dim=8, d_ff=32, max_len=48, seq_len=16,
                          attn_fn=dense_attention)
    params = spec.init(jax.random.PRNGKey(0))
    return spec, params


def _oracle(spec, params, prompt, n, eos_id=None):
    gen = make_generator(spec)
    out = gen(params, prompt[None, :], n, eos_id=eos_id)
    return np.asarray(out)[0]


@pytest.mark.slow
@pytest.mark.parametrize("prefill", [False, True])
def test_engine_matches_generate_exactly(lm, prefill):
    """Varied prompt/output lengths across fewer slots than requests:
    every harvested sequence equals the per-request oracle decode —
    with sequential admission and with parallel prefill."""
    spec, params = lm
    rng = np.random.RandomState(1)
    reqs = [(rng.randint(0, VOCAB, p).astype(np.int32), n)
            for p, n in [(3, 5), (1, 9), (6, 2), (4, 7), (2, 4), (5, 6)]]
    eng = DecodeEngine(spec, params, slots=2, window=24, chunk=4,
                       prefill=prefill)
    ids = [eng.submit(p, n) for p, n in reqs]
    results = eng.run()
    assert sorted(results) == sorted(ids)
    for rid, (prompt, n) in zip(ids, reqs):
        want = _oracle(spec, params, prompt, n)
        np.testing.assert_array_equal(
            results[rid], want,
            err_msg=f"request {rid} (P={prompt.size}, N={n})")
    assert eng.stats.completed == len(reqs)
    # 6 requests through 2 slots: slots were reused.
    assert eng.stats.completed > 2
    assert 0 < eng.stats.slot_utilization <= 1.0
    assert eng.stats.generated_tokens == sum(n for _, n in reqs)
    if prefill:
        # later admissions happen mid-window, behind the tick
        assert eng.stats.prefill_admissions > 0
        assert eng.stats.prefilled_tokens > 0
    else:
        assert eng.stats.prefill_admissions == 0


@pytest.mark.slow
def test_engine_ring_wraps_without_reset(lm):
    """Requests whose spans exceed the remaining window admit anyway —
    the ring wraps each slot's writes mod window (the pre-ring design
    drained the whole pool and rewound the tick here).  Results must
    still be exact (slot/cache ring reuse without zeroing)."""
    spec, params = lm
    rng = np.random.RandomState(2)
    # window 16, spans 13: the ring wraps multiple times over 5 requests
    reqs = [(rng.randint(0, VOCAB, 6).astype(np.int32), 7)
            for _ in range(5)]
    eng = DecodeEngine(spec, params, slots=2, window=16, chunk=5)
    ids = [eng.submit(p, n) for p, n in reqs]
    results = eng.run()
    for rid, (prompt, n) in zip(ids, reqs):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(spec, params, prompt, n))
    # Both slots decode concurrently throughout (no drain stalls): with
    # 5 requests x 6 busy ticks on 2 slots the odd request runs solo at
    # the tail and chunk quantization pads a little, so the ceiling is
    # ~0.75; the old drain-and-rewind design degraded to ~0.5 here.
    assert eng.stats.slot_utilization > 0.6


@pytest.mark.slow
def test_engine_tick_rebase_under_sustained_load(lm):
    """The absolute tick rebases by a multiple of window mid-stream
    (guarding int32 growth under sustained load) without disturbing
    results: ring positions and offset math are invariant under shifts
    that are 0 mod window."""
    spec, params = lm
    rng = np.random.RandomState(11)
    eng = DecodeEngine(spec, params, slots=2, window=16, chunk=4)
    eng._REBASE_AT = 24            # force rebases every few requests
    reqs = [(rng.randint(0, VOCAB, 3).astype(np.int32), 6)
            for _ in range(16)]
    ids, results = [], {}
    max_tick = 0
    for p, n in reqs:              # steady stream: pool never idles
        ids.append(eng.submit(p, n))
        eng.step()
        max_tick = max(max_tick, eng._tick)
        results.update(eng.results())
    while eng.step():
        max_tick = max(max_tick, eng._tick)
    results.update(eng.results())
    # Mid-stream (never at the drained rewind, which zeroes _tick
    # unconditionally): total ticks executed far exceed the rebase
    # threshold, yet the ABSOLUTE tick stayed clamped to
    # < REBASE_AT + window + chunk — the rebase fired.  Without
    # _rebase_tick, max_tick tracks stats.ticks and busts the bound.
    bound = 24 + eng._window + eng._chunk          # 44
    assert eng.stats.ticks > bound + 16
    assert max_tick < bound
    for rid, (p, n) in zip(ids, reqs):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(spec, params, p, n))


@pytest.mark.slow
def test_engine_no_head_of_line_blocking(lm):
    """One long request must not stall the pool: short requests keep
    cycling through the other slot while it runs, so total engine ticks
    stay near the LONG request's span even though total decoded work is
    several times that (the round-4 drain-and-reset design serialized
    here once the tick outgrew the window)."""
    spec, params = lm
    rng = np.random.RandomState(7)
    long_p = rng.randint(0, VOCAB, 4).astype(np.int32)
    long_n = 40                       # span 44 of a 48 window
    shorts = [(rng.randint(0, VOCAB, 3).astype(np.int32), 7)
              for _ in range(6)]      # 6 x span 10 on the other slot
    eng = DecodeEngine(spec, params, slots=2, window=48, chunk=4)
    rid_long = eng.submit(long_p, long_n)
    rid_shorts = [eng.submit(p, n) for p, n in shorts]
    results = eng.run()
    np.testing.assert_array_equal(
        results[rid_long], _oracle(spec, params, long_p, long_n))
    for rid, (p, n) in zip(rid_shorts, shorts):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(spec, params, p, n))
    # All six shorts (60 slot-ticks of work) rode alongside the long
    # request: total ticks ~ long span, nowhere near the serialized sum.
    assert eng.stats.ticks <= long_n + 4 + 3 * 4


def test_engine_eos_early_stop(lm):
    """A generated eos truncates the result (eos kept) and frees the
    slot early; prompt-resident eos is data, not a stop."""
    spec, params = lm
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, VOCAB, 4).astype(np.int32)
    # Find the greedy continuation, then use its SECOND generated token
    # as the eos id so the engine must stop after two tokens.
    free = _oracle(spec, params, prompt, 6)
    eos = int(free[prompt.size + 1])
    if eos == free[prompt.size]:  # pragma: no cover - degenerate repeat
        pytest.skip("greedy repeats a token; eos choice ambiguous")
    eng = DecodeEngine(spec, params, slots=2, window=24, chunk=3,
                       eos_id=eos)
    # prompt containing the eos token must not stop the row
    prompt_with_eos = np.concatenate(
        [[np.int32(eos)], prompt]).astype(np.int32)
    r1 = eng.submit(prompt, 6)
    r2 = eng.submit(prompt_with_eos, 3)
    results = eng.run()
    want = _oracle(spec, params, prompt, 6, eos_id=eos)
    # oracle pads with eos after the stop; engine truncates after it
    np.testing.assert_array_equal(results[r1],
                                  want[:prompt.size + 2])
    assert results[r1][-1] == eos
    assert results[r2].size == prompt_with_eos.size + 3 or \
        results[r2][-1] == eos


def test_engine_interleaved_submit(lm):
    """step()/results(): submitting while decoding is in flight — the
    continuous-batching loop proper."""
    spec, params = lm
    rng = np.random.RandomState(4)
    p1 = rng.randint(0, VOCAB, 3).astype(np.int32)
    p2 = rng.randint(0, VOCAB, 2).astype(np.int32)
    eng = DecodeEngine(spec, params, slots=2, window=32, chunk=2)
    r1 = eng.submit(p1, 4)
    assert eng.step()            # starts decoding r1
    r2 = eng.submit(p2, 5)       # lands mid-flight
    while eng.step():
        pass
    results = eng.results()
    np.testing.assert_array_equal(results[r1], _oracle(spec, params, p1, 4))
    np.testing.assert_array_equal(results[r2], _oracle(spec, params, p2, 5))


def test_engine_partial_streaming(lm):
    """partial(): an in-flight request's tokens-so-far grow between
    chunks and are a prefix of the final result."""
    spec, params = lm
    rng = np.random.RandomState(9)
    prompt = rng.randint(0, VOCAB, 3).astype(np.int32)
    eng = DecodeEngine(spec, params, slots=1, window=32, chunk=2)
    rid = eng.submit(prompt, 8)
    assert eng.partial(rid) is None          # still queued
    snapshots = []
    while eng.step():
        part = eng.partial(rid)
        if part is not None:
            snapshots.append(part.copy())
    final = eng.results()[rid]
    assert eng.partial(rid) is None          # completed -> not partial
    assert len(snapshots) >= 2
    assert any(s.size < final.size for s in snapshots)
    for s in snapshots:
        np.testing.assert_array_equal(s, final[:s.size])


@pytest.mark.slow
def test_engine_mesh_sharded_slots(lm):
    """Multi-chip serving: the slot pool sharded over a mesh axis gives
    exactly the per-request oracle results, and the state buffers keep
    their shardings chunk to chunk (donation preserves placement)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    spec, params = lm
    devices = np.array(jax.devices()[:4])
    mesh = Mesh(devices, ("data",))
    rng = np.random.RandomState(12)
    reqs = [(rng.randint(0, VOCAB, p).astype(np.int32), n)
            for p, n in [(3, 5), (1, 7), (4, 4), (2, 6), (5, 3), (2, 8)]]
    eng = DecodeEngine(spec, params, slots=4, window=24, chunk=4,
                       mesh=mesh)
    ids = [eng.submit(p, n) for p, n in reqs]
    results = eng.run()
    for rid, (prompt, n) in zip(ids, reqs):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(spec, params, prompt, n))
    # the slot axis stays sharded after many chunk/prefill programs —
    # both the caches and the token buffer (the latter is also mutated
    # by the host-driven prompt-write program)
    want = NamedSharding(mesh, PartitionSpec(None, None, "data"))
    assert eng._kc.sharding.is_equivalent_to(want, eng._kc.ndim)
    want_row = NamedSharding(mesh, PartitionSpec("data"))
    assert eng._tokens.sharding.is_equivalent_to(want_row,
                                                 eng._tokens.ndim)

    with pytest.raises(ValueError, match="must divide"):
        DecodeEngine(spec, params, slots=3, window=24, mesh=mesh)
    with pytest.raises(ValueError, match="not in mesh axes"):
        DecodeEngine(spec, params, slots=4, window=24, mesh=mesh,
                     slot_axis="model")


@pytest.mark.slow
def test_engine_tp_params_with_sharded_slots(lm):
    """The composition the docstring promises: model-axis (TP) sharded
    params AND a data-axis sharded slot pool on one 2-D mesh, token-
    exact vs host-layout per-request decode."""
    import optax

    from autodist_tpu.autodist import (AutoDist,
                                       _reset_default_autodist_for_testing)
    from autodist_tpu.strategy import Parallax

    spec, params = lm
    _reset_default_autodist_for_testing()
    ad = AutoDist(strategy_builder=Parallax(),
                  mesh_axes={"model": 2, "data": 4})
    with ad.scope():
        ad.capture(params=params, optimizer=optax.sgd(0.01),
                   loss_fn=spec.loss_fn, sparse_vars=spec.sparse_vars)
    sess = ad.create_distributed_session()

    rng = np.random.RandomState(13)
    reqs = [(rng.randint(0, VOCAB, p).astype(np.int32), n)
            for p, n in [(3, 5), (2, 7), (4, 3), (1, 6)]]
    eng = DecodeEngine(spec, sess.sharded_params, slots=4, window=24,
                       chunk=4, mesh=sess.mesh, slot_axis="data")
    ids = [eng.submit(p, n) for p, n in reqs]
    results = eng.run()
    for rid, (prompt, n) in zip(ids, reqs):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(spec, params, prompt, n))


def test_engine_cancel(lm):
    """cancel(): queued requests vanish; an in-flight request frees its
    slot for the next admission; completed/unknown ids return False."""
    spec, params = lm
    rng = np.random.RandomState(10)
    p1 = rng.randint(0, VOCAB, 3).astype(np.int32)
    p2 = rng.randint(0, VOCAB, 2).astype(np.int32)
    p3 = rng.randint(0, VOCAB, 2).astype(np.int32)
    eng = DecodeEngine(spec, params, slots=1, window=32, chunk=2)
    r1 = eng.submit(p1, 10)
    r2 = eng.submit(p2, 4)
    r3 = eng.submit(p3, 3)
    assert eng.cancel(r2)                    # still queued
    assert eng.step()                        # r1 now in flight
    assert eng.cancel(r1)                    # in flight -> freed
    results = eng.run()
    assert sorted(results) == [r3]           # only r3 completes
    np.testing.assert_array_equal(results[r3], _oracle(spec, params, p3, 3))
    assert not eng.cancel(r3)                # completed
    assert not eng.cancel(99)                # unknown


def test_engine_sampling_smoke(lm):
    """Temperature path: shapes/ranges sane (the key schedule differs
    from generate's, so no token parity is claimed)."""
    spec, params = lm
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, VOCAB, 3).astype(np.int32)
    eng = DecodeEngine(spec, params, slots=1, window=16, chunk=4,
                       temperature=0.8, top_k=10,
                       rng=jax.random.PRNGKey(7))
    rid = eng.submit(prompt, 5)
    (seq,) = eng.run().values()
    assert seq.shape == (8,)
    np.testing.assert_array_equal(seq[:3], prompt)
    assert np.all((seq >= 0) & (seq < VOCAB))
    del rid


@pytest.mark.slow
def test_engine_batched_prefill_single_dispatch(lm):
    """Two slots retiring at the same boundary admit their replacements
    through ONE batched prefill program (prefill_dispatches counts
    dispatches; prefill_admissions counts requests)."""
    spec, params = lm
    rng = np.random.RandomState(14)
    eng = DecodeEngine(spec, params, slots=2, window=32, chunk=16)
    # wave 1: identical spans -> both slots retire at the same tick
    wave1 = [(rng.randint(0, VOCAB, 3).astype(np.int32), 5)
             for _ in range(2)]
    # wave 2: admitted together at that boundary, behind the tick
    wave2 = [(rng.randint(0, VOCAB, 2).astype(np.int32), 4)
             for _ in range(2)]
    ids = [eng.submit(p, n) for p, n in wave1 + wave2]
    results = eng.run()
    for rid, (prompt, n) in zip(ids, wave1 + wave2):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(spec, params, prompt, n))
    # Ring admission prefills EVERY wave (wave 1 lands behind tick 0 at
    # wrapped ring positions): 4 admissions in exactly 2 batched
    # dispatches — one per boundary, never one per request.
    assert eng.stats.prefill_admissions == 4
    assert eng.stats.prefill_dispatches == 2


def test_engine_prefill_dedup_shared_prompt(lm):
    """Identical prompts admitted at one boundary (the n-samples-per-
    prompt / system-prompt fan-out case) compute their prefill ONCE:
    dedup hits recorded, greedy results still oracle-exact, and under
    temperature the slots draw independent samples."""
    spec, params = lm
    rng = np.random.RandomState(15)
    shared = rng.randint(0, VOCAB, 3).astype(np.int32)
    eng = DecodeEngine(spec, params, slots=2, window=32, chunk=16)
    # wave 1 occupies both slots to push the tick past the prompt size
    w1 = [(rng.randint(0, VOCAB, 3).astype(np.int32), 5)
          for _ in range(2)]
    ids1 = [eng.submit(p, n) for p, n in w1]
    # wave 2: the SAME prompt twice -> one prefill row, two slots
    ids2 = [eng.submit(shared, 4) for _ in range(2)]
    results = eng.run()
    for rid, (p, n) in zip(ids1, w1):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(spec, params, p, n))
    want = _oracle(spec, params, shared, 4)
    for rid in ids2:
        np.testing.assert_array_equal(results[rid], want)
    assert eng.stats.prefill_dedup_hits == 1
    # one dispatch per admission boundary (wave 1 + wave 2)
    assert eng.stats.prefill_dispatches == 2

    # temperature: shared prefill row, but per-slot independent draws
    eng2 = DecodeEngine(spec, params, slots=2, window=32, chunk=16,
                        temperature=1.0, rng=jax.random.PRNGKey(3))
    w1b = [(rng.randint(0, VOCAB, 3).astype(np.int32), 5)
           for _ in range(2)]
    for p, n in w1b:
        eng2.submit(p, n)
    ids2b = [eng2.submit(shared, 8) for _ in range(2)]
    res2 = eng2.run()
    a, bseq = res2[ids2b[0]], res2[ids2b[1]]
    assert eng2.stats.prefill_dedup_hits >= 1
    # overwhelmingly likely to differ somewhere over 8 sampled tokens
    assert not np.array_equal(a, bseq)


@pytest.mark.slow
def test_engine_prefill_single_token_requests(lm):
    """max_new_tokens=1 through the prefill path finishes a request AT
    admission — the scheduler must keep draining the queue without
    running idle chunks."""
    spec, params = lm
    rng = np.random.RandomState(7)
    # a longer opener so later admissions happen at tick >= P
    opener = rng.randint(0, VOCAB, 4).astype(np.int32)
    reqs = [(opener, 6)] + [
        (rng.randint(0, VOCAB, 3).astype(np.int32), 1) for _ in range(5)]
    eng = DecodeEngine(spec, params, slots=2, window=32, chunk=4)
    ids = [eng.submit(p, n) for p, n in reqs]
    results = eng.run()
    assert sorted(results) == sorted(ids)
    for rid, (prompt, n) in zip(ids, reqs):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(spec, params, prompt, n))
    assert eng.stats.prefill_admissions >= 4


@pytest.mark.slow
def test_engine_with_session_sharded_params(lm):
    """The engine decodes straight off a session's mesh-sharded params
    (vocab-sharded embed under Parallax on a model-axis mesh), exactly
    matching host-layout results — continuous batching composes with the
    training shardings (GSPMD propagates through the chunk program)."""
    import optax

    from autodist_tpu.autodist import (AutoDist,
                                       _reset_default_autodist_for_testing)
    from autodist_tpu.strategy import Parallax

    spec, params = lm
    _reset_default_autodist_for_testing()
    ad = AutoDist(strategy_builder=Parallax(),
                  mesh_axes={"model": 2, "data": 4})
    with ad.scope():
        ad.capture(params=params, optimizer=optax.sgd(0.01),
                   loss_fn=spec.loss_fn, sparse_vars=spec.sparse_vars)
    sess = ad.create_distributed_session()

    rng = np.random.RandomState(8)
    reqs = [(rng.randint(0, VOCAB, p).astype(np.int32), n)
            for p, n in [(3, 5), (2, 6), (4, 4)]]
    eng = DecodeEngine(spec, sess.sharded_params, slots=2, window=24,
                       chunk=4)
    ids = [eng.submit(p, n) for p, n in reqs]
    results = eng.run()
    for rid, (prompt, n) in zip(ids, reqs):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(spec, params, prompt, n))


@pytest.mark.slow
def test_engine_long_prompt_prefill(lm):
    """A long (130-token) prompt stays oracle-exact through prefill;
    its pow-2 bucket overruns the window so it also exercises the
    exact-size fallback."""
    spec_long = transformer_lm(vocab_size=VOCAB, num_layers=2,
                               num_heads=2, head_dim=8, d_ff=32,
                               max_len=200, seq_len=16,
                               attn_fn=dense_attention)
    params = spec_long.init(jax.random.PRNGKey(4))
    rng = np.random.RandomState(16)
    short = rng.randint(0, VOCAB, 2).astype(np.int32)
    long_p = rng.randint(0, VOCAB, 130).astype(np.int32)
    eng = DecodeEngine(spec_long, params, slots=1, window=192, chunk=32)
    r1 = eng.submit(short, 140)          # drives the tick past 130
    r2 = eng.submit(long_p, 6)           # prefill-admitted, P=130
    results = eng.run()
    np.testing.assert_array_equal(
        results[r1], _oracle(spec_long, params, short, 140))
    np.testing.assert_array_equal(
        results[r2], _oracle(spec_long, params, long_p, 6))
    # both requests prefill under ring admission (130 + 2 tokens)
    assert eng.stats.prefill_admissions == 2
    assert eng.stats.prefilled_tokens == 132


@pytest.mark.slow
def test_engine_quantized_params(lm):
    """Weight-only int8 tree through the engine: matches the int8
    generate() oracle exactly (the tick math routes through the same
    quantized kernels)."""
    from autodist_tpu.models.quantize import quantize_lm_params
    spec, params = lm
    qp = quantize_lm_params(params)
    rng = np.random.RandomState(6)
    reqs = [(rng.randint(0, VOCAB, p).astype(np.int32), n)
            for p, n in [(3, 4), (2, 6), (5, 3)]]
    eng = DecodeEngine(spec, qp, slots=2, window=20, chunk=4)
    ids = [eng.submit(p, n) for p, n in reqs]
    results = eng.run()
    gen = make_generator(spec)
    for rid, (prompt, n) in zip(ids, reqs):
        want = np.asarray(gen(qp, prompt[None, :], n))[0]
        np.testing.assert_array_equal(results[rid], want)


def test_engine_poisoned_after_failed_dispatch(lm, monkeypatch):
    """A device dispatch failing mid-flight (buffers already donated)
    must poison the engine with a clear error, not decode garbage."""
    import autodist_tpu.serving.engine as eng_mod

    spec, params = lm
    eng = DecodeEngine(spec, params, slots=1, window=16, chunk=2)
    eng.submit(np.arange(2, dtype=np.int32), 4)

    def boom(*a, **k):
        raise RuntimeError("device lost")

    monkeypatch.setattr(eng_mod, "_chunk_program", boom)
    with pytest.raises(RuntimeError, match="device lost"):
        eng.run()
    monkeypatch.undo()
    with pytest.raises(RuntimeError, match="poisoned"):
        eng.step()
    with pytest.raises(RuntimeError, match="poisoned"):
        eng.submit(np.arange(2, dtype=np.int32), 2)
    with pytest.raises(RuntimeError, match="poisoned"):
        eng.partial(0)
    assert eng.results() == {}   # host-side salvage still works

    # reset() revives the engine: fresh buffers, same compiled programs
    eng.reset()
    prompt = np.arange(3, dtype=np.int32)
    rid = eng.submit(prompt, 4)
    out = eng.run()
    np.testing.assert_array_equal(out[rid],
                                  _oracle(spec, params, prompt, 4))


def test_engine_validation(lm):
    spec, params = lm
    eng = DecodeEngine(spec, params, slots=1, window=8)
    with pytest.raises(ValueError, match="exceeds the engine window"):
        eng.submit(np.arange(5, dtype=np.int32), 10)
    with pytest.raises(ValueError, match="at least one token"):
        eng.submit(np.zeros(0, np.int32), 2)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.arange(2, dtype=np.int32), 0)
    with pytest.raises(ValueError, match="out of vocab"):
        eng.submit(np.array([VOCAB + 3], np.int32), 2)
    with pytest.raises(ValueError, match="needs temperature"):
        DecodeEngine(spec, params, window=8, top_k=5)
    with pytest.raises(ValueError, match="max_len"):
        DecodeEngine(spec, params, window=4096)


def test_engine_per_request_sampling_knobs(lm):
    """temperature/eos_id are PER-REQUEST (traced per-slot vectors, one
    compiled program): a greedy request stays oracle-exact while a
    sampled request decodes in the adjacent slot, and a per-request eos
    stops only its own slot."""
    spec, params = lm
    rng = np.random.RandomState(21)
    p1 = rng.randint(0, VOCAB, 3).astype(np.int32)
    p2 = rng.randint(0, VOCAB, 4).astype(np.int32)

    eng = DecodeEngine(spec, params, slots=2, window=24, chunk=4,
                       rng=jax.random.PRNGKey(7))
    r_greedy = eng.submit(p1, 8)                      # default temp 0
    r_sampled = eng.submit(p2, 8, temperature=1.0)    # per-request
    results = eng.run()
    np.testing.assert_array_equal(results[r_greedy],
                                  _oracle(spec, params, p1, 8))
    sampled = results[r_sampled]
    assert sampled.size == p2.size + 8
    assert np.all((sampled >= 0) & (sampled < VOCAB))

    # per-request eos: pick the greedy continuation's 3rd token as eos
    # for ONE of two otherwise-identical greedy requests.
    free = _oracle(spec, params, p1, 8)
    eos = int(free[p1.size + 2])
    if eos in (int(free[p1.size]), int(free[p1.size + 1])):
        pytest.skip("greedy repeats; eos choice ambiguous")
    eng2 = DecodeEngine(spec, params, slots=2, window=24, chunk=4)
    r_stop = eng2.submit(p1, 8, eos_id=eos)
    r_full = eng2.submit(p1, 8)
    out = eng2.run()
    np.testing.assert_array_equal(out[r_stop], free[:p1.size + 3])
    assert out[r_stop][-1] == eos
    np.testing.assert_array_equal(out[r_full], free)  # untouched slot


def test_engine_per_request_temperature_needs_rng(lm):
    """A greedy-built engine without an explicit rng refuses a sampled
    request loudly (a silent fixed key would sample identical streams)."""
    spec, params = lm
    eng = DecodeEngine(spec, params, slots=1, window=16)
    with pytest.raises(ValueError, match="rng"):
        eng.submit(np.arange(2, dtype=np.int32), 4, temperature=0.7)
    with pytest.raises(ValueError, match="temperature"):
        eng.submit(np.arange(2, dtype=np.int32), 4, temperature=-1.0)
    with pytest.raises(ValueError, match="eos_id"):
        eng.submit(np.arange(2, dtype=np.int32), 4, eos_id=VOCAB + 3)


@pytest.mark.slow
def test_engine_per_request_validation_edges(lm):
    """NaN/inf/f32-underflow temperatures are rejected; eos_id=-1
    explicitly disables an engine-default eos for one request."""
    spec, params = lm
    rng = np.random.RandomState(31)
    # find a prompt whose greedy continuation has a usable (non-tied,
    # non-initial) eos candidate
    for _ in range(20):
        prompt = rng.randint(0, VOCAB, 3).astype(np.int32)
        free = _oracle(spec, params, prompt, 6)
        eos = int(free[prompt.size + 1])
        if eos not in (int(free[prompt.size]), *prompt.tolist()):
            break
    else:  # pragma: no cover - wildly unlikely
        pytest.skip("no unambiguous eos candidate found")
    eng = DecodeEngine(spec, params, slots=2, window=24, chunk=3,
                       eos_id=eos, rng=jax.random.PRNGKey(1))
    for bad in (float("nan"), float("inf"), 1e-300):
        with pytest.raises(ValueError):
            eng.submit(np.arange(2, dtype=np.int32), 4, temperature=bad)
    r_default = eng.submit(prompt, 6)
    r_noeos = eng.submit(prompt, 6, eos_id=-1)
    out = eng.run()
    assert out[r_default][-1] == eos and out[r_default].size < 9
    np.testing.assert_array_equal(out[r_noeos], free)   # ran to length


@pytest.mark.parametrize("prefill", [False, True])
def test_engine_prefix_cache_token_exact(lm, prefill):
    """A registered shared prefix (system prompt) is held ONCE and
    attended as cached context: each request's output equals the full
    generate over concat(prefix, prompt) with the prefix stripped —
    through both admission paths, with a non-prefix request decoding in
    the adjacent slot concurrently."""
    spec, params = lm
    rng = np.random.RandomState(17)
    prefix = rng.randint(0, VOCAB, 5).astype(np.int32)
    p1 = rng.randint(0, VOCAB, 3).astype(np.int32)
    p2 = rng.randint(0, VOCAB, 4).astype(np.int32)

    eng = DecodeEngine(spec, params, slots=2, window=24, chunk=4,
                       prefill=prefill)
    assert eng.set_prefix(prefix) == 5
    assert eng.prefix_len == 5
    r_pre = eng.submit(p1, 7, use_prefix=True)
    r_plain = eng.submit(p2, 6)                   # no prefix, same batch
    results = eng.run()

    want_full = _oracle(spec, params, np.concatenate([prefix, p1]), 7)
    np.testing.assert_array_equal(results[r_pre], want_full[prefix.size:],
                                  err_msg="prefix-cached decode")
    np.testing.assert_array_equal(results[r_plain],
                                  _oracle(spec, params, p2, 6),
                                  err_msg="non-prefix slot disturbed")
    # prefix K/V were not recomputed per admission
    assert eng.stats.prompt_tokens == p1.size + p2.size

    # slot REUSE under the prefix: a second wave still exact
    r3 = eng.submit(p2, 5, use_prefix=True)
    out2 = eng.run()
    want3 = _oracle(spec, params, np.concatenate([prefix, p2]), 5)
    np.testing.assert_array_equal(out2[r3], want3[prefix.size:])

    # clear_prefix restores plain behavior
    eng.clear_prefix()
    r4 = eng.submit(p1, 4)
    np.testing.assert_array_equal(eng.run()[r4],
                                  _oracle(spec, params, p1, 4))


def test_engine_prefix_validation(lm):
    spec, params = lm
    eng = DecodeEngine(spec, params, slots=1, window=16, chunk=2)
    with pytest.raises(ValueError, match="no prefix"):
        eng.submit(np.arange(2, dtype=np.int32), 3, use_prefix=True)
    eng.set_prefix(np.arange(4, dtype=np.int32))
    # prefix + span must fit the model's pos_embed rows (max_len 48)
    with pytest.raises(ValueError, match="max_len"):
        eng.set_prefix(np.arange(47, dtype=np.int32))
    # a busy engine accepts a prefix swap: in-flight requests keep the
    # generation they pinned (exactness pinned in
    # tests/test_serving_scheduler.py::
    # test_slot_engine_mid_flight_prefix_swap_pins_readers)
    eng.submit(np.arange(2, dtype=np.int32), 6)
    assert eng.step()
    eng.set_prefix(np.arange(3, dtype=np.int32))
    while eng.step():
        pass
    eng.results()
    assert eng.prefix_len == 3


@pytest.mark.slow
def test_engine_prefix_bucket_edges(lm):
    """The pow-2 buckets must not outrun pos_embed (max_len 48 here):
    (a) a prompt whose bucket extends past max_len under a prefix —
    position ids clip, pad-row K/V are overwritten before any read;
    (b) a prefix whose own bucket exceeds max_len falls back to exact
    size.  Both stay token-exact vs the concat oracle."""
    spec, params = lm
    rng = np.random.RandomState(23)

    # (a) the clip path proper: prefix 35 + prompt 9 (bucket 16 fits
    # window 16, so no exact-size fallback) -> pad bucket positions
    # 44..50 overrun max_len 48 and CLIP; their K/V land at ring >= t0
    # and are overwritten before any read.  Real rows stay exact.
    prefix = rng.randint(0, VOCAB, 35).astype(np.int32)
    prompt = rng.randint(0, VOCAB, 9).astype(np.int32)
    eng = DecodeEngine(spec, params, slots=2, window=16, chunk=4)
    eng.set_prefix(prefix)
    rid = eng.submit(prompt, 3, use_prefix=True)
    out = eng.run()
    want = _oracle(spec, params, np.concatenate([prefix, prompt]), 3)
    np.testing.assert_array_equal(out[rid], want[prefix.size:])

    # (b) prefix 40: pow-2 bucket 64 > max_len 48 -> exact fallback
    prefix_b = rng.randint(0, VOCAB, 40).astype(np.int32)
    eng2 = DecodeEngine(spec, params, slots=1, window=8, chunk=2)
    assert eng2.set_prefix(prefix_b) == 40
    p_small = rng.randint(0, VOCAB, 2).astype(np.int32)
    rid2 = eng2.submit(p_small, 3, use_prefix=True)
    out2 = eng2.run()
    want2 = _oracle(spec, params,
                    np.concatenate([prefix_b, p_small]), 3)
    np.testing.assert_array_equal(out2[rid2], want2[prefix_b.size:])


def test_engine_rejects_below_floor_temperature(lm):
    """Temperatures in (0, 1e-6) are rejected at submit — the sampler's
    divide floor would otherwise silently clamp them (ADVICE r5 low #1);
    0 (greedy) and the floor itself stay accepted."""
    spec, params = lm
    eng = DecodeEngine(spec, params, slots=1, window=16, chunk=2,
                       rng=jax.random.PRNGKey(0))
    prompt = np.arange(2, dtype=np.int32)
    for bad in (1e-7, 9.9e-7, 1e-20):
        with pytest.raises(ValueError, match="floor"):
            eng.submit(prompt, 2, temperature=bad)
    eng.submit(prompt, 2, temperature=0.0)      # greedy: fine
    eng.submit(prompt, 2, temperature=1e-6)     # exactly the floor: fine
    eng.run()


def test_engine_rebase_resets_inactive_slot_bounds(lm):
    """_rebase_tick zeroes inactive slots' start/p_end/end instead of
    shifting them: a slot that never re-admits can no longer accumulate
    -shift per rebase toward int32 wrap (ADVICE r5 low #2)."""
    spec, params = lm
    rng = np.random.RandomState(21)
    eng = DecodeEngine(spec, params, slots=3, window=16, chunk=4)
    eng._REBASE_AT = 24
    # slot pool wider than the stream: slot 2 admits once, then idles
    first = eng.submit(rng.randint(0, VOCAB, 3).astype(np.int32), 4)
    while eng.step():
        pass
    eng.results()
    assert not eng._active.any()
    # sustained single-slot stream forces repeated rebases
    ids = []
    for _ in range(12):
        ids.append(eng.submit(rng.randint(0, VOCAB, 3).astype(np.int32), 6))
        eng.step()
        eng.results()
    while eng.step():
        pass
    eng.results()
    # every inactive slot's bounds were reset at the last rebase: they
    # can never be more negative than one rebase window's shift.
    inactive = ~eng._active
    assert inactive.all()
    for arr in (eng._start, eng._p_end, eng._end):
        assert int(arr[inactive].min()) > -(1 << 24), arr
    del first, ids


@pytest.mark.parametrize("wrap", [False, True])
def test_engine_prefill_contiguous_and_wrapped_paths_token_exact(lm, wrap):
    """Token-exactness pin for BOTH prefill cache-write paths: the
    contiguous dynamic_update_slice fast path (no ring wrap) and the
    mod-window scatter path (wrapped admission).  The wrapped case
    arises only once the tick outgrows the window (t0 % window < P)."""
    spec, params = lm
    rng = np.random.RandomState(33)
    eng = DecodeEngine(spec, params, slots=1, window=16, chunk=4)
    reqs = [(rng.randint(0, VOCAB, 6).astype(np.int32), 7)]
    if wrap:
        # run enough sequential requests that an admission lands with
        # t0 % 16 < 6 (the single slot serializes them, walking t0
        # through every residue)
        reqs = [(rng.randint(0, VOCAB, 6).astype(np.int32), 7)
                for _ in range(5)]
    ids = [eng.submit(p, n) for p, n in reqs]
    results = eng.run()
    for rid, (p, n) in zip(ids, reqs):
        np.testing.assert_array_equal(
            results[rid], _oracle(spec, params, p, n),
            err_msg=f"wrap={wrap} request {rid}")
    if wrap:
        assert eng.stats.prefill_dispatches >= 2


@pytest.mark.slow
def test_engine_prefill_mixed_wrapness_boundary(lm):
    """One boundary admitting a wrapping and a non-wrapping prompt
    dispatches them as separate (static-wrapness) programs and both
    stay oracle-exact."""
    spec, params = lm
    rng = np.random.RandomState(35)
    eng = DecodeEngine(spec, params, slots=2, window=16, chunk=4)
    # opener pair retires together at a tick t0 with 0 < t0 % 16 < 8
    openers = [(rng.randint(0, VOCAB, 3).astype(np.int32), 7)
               for _ in range(2)]
    # next wave: one long prompt (wraps when t0 % 16 < 8) + one short
    wave2 = [(rng.randint(0, VOCAB, 8).astype(np.int32), 3),
             (rng.randint(0, VOCAB, 1).astype(np.int32), 3)]
    ids = [eng.submit(p, n) for p, n in openers + wave2]
    results = eng.run()
    for rid, (p, n) in zip(ids, openers + wave2):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(spec, params, p, n))
