"""PagedDecodeEngine vs per-request `generate` (oracle), plus the
scheduler surface: SLO admission order, bounded-queue backpressure,
block-budget deferral, prefix reuse, chunked prefill, slot/block
recycling, and the no-leak invariant.

The engine's claim is the slot engine's — token-exact greedy decode —
carried over to the paged layout: block-table indirection plus masked
attention over gathered pool windows must reproduce the single-request
KV-cache decode bit-for-bit, including requests admitted mid-run and
requests whose prompt prefix comes from the trie instead of prefill.
"""
import jax
import numpy as np
import pytest

from autodist_tpu.models.generate import make_generator
from autodist_tpu.models.transformer import dense_attention
from autodist_tpu.models.transformer_lm import transformer_lm
from autodist_tpu.serving import (AdmissionError, PagedDecodeEngine,
                                  SLO_LATENCY, SLO_THROUGHPUT)

pytestmark = pytest.mark.serving

VOCAB = 61
# One shared engine geometry across the file: the compiled paged
# programs live in a module-scope jit cache, so identical shapes
# compile once per test process.
GEOM = dict(slots=2, window=32, block_size=8, num_blocks=24, chunk=4)


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


def test_paged_matches_generate_exactly(lm):
    """Varied prompt/output lengths across fewer slots than requests:
    every harvested sequence equals the per-request oracle, blocks all
    recycle, and the pool shows no leak after the drain."""
    spec, params = lm
    rng = np.random.RandomState(1)
    reqs = [(rng.randint(0, VOCAB, p).astype(np.int32), n)
            for p, n in [(3, 5), (1, 9), (6, 2), (4, 7), (2, 4), (5, 6)]]
    eng = PagedDecodeEngine(spec, params, **GEOM)
    ids = [eng.submit(p, n) for p, n in reqs]
    results = eng.run()
    assert sorted(results) == sorted(ids)
    for rid, (prompt, n) in zip(ids, reqs):
        np.testing.assert_array_equal(
            results[rid], _oracle(spec, params, prompt, n),
            err_msg=f"request {rid} (P={prompt.size}, N={n})")
    assert eng.stats.completed == len(reqs) > eng._slots
    assert eng.stats.generated_tokens == sum(n for _, n in reqs)
    assert 0 < eng.stats.slot_utilization <= 1.0
    eng.assert_no_leaks()


def test_paged_mid_run_admission_exact(lm):
    """The acceptance-criterion case: requests admitted WHILE the batch
    decodes are still oracle-exact (continuous batching proper)."""
    spec, params = lm
    rng = np.random.RandomState(4)
    eng = PagedDecodeEngine(spec, params, **GEOM)
    p1 = rng.randint(0, VOCAB, 3).astype(np.int32)
    p2 = rng.randint(0, VOCAB, 2).astype(np.int32)
    p3 = rng.randint(0, VOCAB, 5).astype(np.int32)
    r1 = eng.submit(p1, 6)
    assert eng.step()                 # r1 decoding
    r2 = eng.submit(p2, 5)            # joins mid-run
    eng.step()
    r3 = eng.submit(p3, 4)            # and another
    while eng.step():
        pass
    results = eng.results()
    np.testing.assert_array_equal(results[r1], _oracle(spec, params, p1, 6))
    np.testing.assert_array_equal(results[r2], _oracle(spec, params, p2, 5))
    np.testing.assert_array_equal(results[r3], _oracle(spec, params, p3, 4))
    eng.assert_no_leaks()


def test_paged_prefix_reuse_skips_prefill(lm):
    """Requests sharing a cached prompt prefix reference the trie's
    blocks instead of recomputing them — exact output, non-zero cached
    token count, and the cached blocks are genuinely shared (refcount
    via the no-leak check after the drain)."""
    spec, params = lm
    rng = np.random.RandomState(2)
    shared = rng.randint(0, VOCAB, 17).astype(np.int32)   # 2 full blocks
    tails = [rng.randint(0, VOCAB, 3).astype(np.int32) for _ in range(3)]
    prompts = [np.concatenate([shared, t]) for t in tails]
    eng = PagedDecodeEngine(spec, params, slots=2, window=32,
                            block_size=8, num_blocks=40, chunk=4)
    r0 = eng.submit(prompts[0], 5)                        # warms the trie
    out = eng.run()
    np.testing.assert_array_equal(out[r0],
                                  _oracle(spec, params, prompts[0], 5))
    assert eng.stats.cached_prompt_tokens == 0
    assert len(eng.trie) == 2
    ids = [eng.submit(p, 6) for p in prompts[1:]]
    out = eng.run()
    for rid, p in zip(ids, prompts[1:]):
        np.testing.assert_array_equal(
            out[rid], _oracle(spec, params, p, 6),
            err_msg="prefix-hit request diverged from oracle")
    # both followers skipped the 16 shared tokens
    assert eng.stats.cached_prompt_tokens == 32
    assert eng.stats.prefix_requests == 2
    assert eng.stats.prefix_hit_rate > 0
    assert eng.trie.stats.lookup_hits == 2
    eng.assert_no_leaks()


def test_paged_chunked_prefill_interleaves_and_stays_exact(lm):
    """A long prompt charges in prefill_chunk pieces BETWEEN decode
    chunks: the short request keeps generating while the long prompt
    prefills, and both stay oracle-exact."""
    spec, params = lm
    rng = np.random.RandomState(3)
    eng = PagedDecodeEngine(spec, params, slots=2, window=32,
                            block_size=8, num_blocks=24, chunk=4,
                            prefill_chunk=5)
    short = rng.randint(0, VOCAB, 3).astype(np.int32)
    long_p = rng.randint(0, VOCAB, 23).astype(np.int32)
    ra = eng.submit(short, 12)
    eng.step()                        # short decoding
    ticks_before = eng.stats.ticks
    rb = eng.submit(long_p, 6)        # 23 tokens -> 5 chunks of <=5
    while eng.step():
        pass
    results = eng.results()
    np.testing.assert_array_equal(results[ra],
                                  _oracle(spec, params, short, 12))
    np.testing.assert_array_equal(results[rb],
                                  _oracle(spec, params, long_p, 6))
    assert eng.stats.prefill_chunks >= 5 + 1
    # decode ticks ran during the long prefill (interleaving, not a
    # stall-the-world prefill)
    assert eng.stats.ticks > ticks_before
    eng.assert_no_leaks()


def test_paged_slo_priority_admission(lm):
    """With one slot, a latency-class request submitted AFTER a
    throughput-class request is admitted (and completes) first."""
    spec, params = lm
    rng = np.random.RandomState(5)
    eng = PagedDecodeEngine(spec, params, slots=1, window=32,
                            block_size=8, num_blocks=24, chunk=4)
    opener = eng.submit(rng.randint(0, VOCAB, 2).astype(np.int32), 4)
    eng.step()                                       # slot busy
    r_tp = eng.submit(rng.randint(0, VOCAB, 2).astype(np.int32), 3,
                      slo=SLO_THROUGHPUT)
    r_lat = eng.submit(rng.randint(0, VOCAB, 2).astype(np.int32), 3,
                       slo=SLO_LATENCY)
    order = []
    while eng.step():
        for rid in eng.results():
            order.append(rid)
    for rid in eng.results():
        order.append(rid)
    assert order.index(r_lat) < order.index(r_tp)
    assert order[0] == opener
    eng.assert_no_leaks()


def test_paged_bounded_queue_backpressure(lm):
    """A full SLO queue rejects with the typed AdmissionError and a
    usable Retry-After hint; the other class's queue is unaffected."""
    spec, params = lm
    rng = np.random.RandomState(6)
    eng = PagedDecodeEngine(spec, params, slots=1, window=32,
                            block_size=8, num_blocks=24, chunk=4,
                            max_queue=2)
    prompts = [rng.randint(0, VOCAB, 2).astype(np.int32)
               for _ in range(4)]
    ids = [eng.submit(p, 3) for p in prompts[:2]]    # queue now full
    with pytest.raises(AdmissionError) as exc:
        eng.submit(prompts[2], 3)
    assert exc.value.retry_after_s > 0
    assert eng.stats.rejected_full == 1
    # throughput class still admits
    ids.append(eng.submit(prompts[3], 3, slo=SLO_THROUGHPUT))
    results = eng.run()
    for rid, p in zip(ids, [prompts[0], prompts[1], prompts[3]]):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(spec, params, p, 3))
    eng.assert_no_leaks()


def test_paged_block_budget_defers_admission(lm):
    """Pool too small for two concurrent requests: the second DEFERS
    (stays queued, counted) until the first frees its blocks — decode
    never sees a mid-step OOM — then completes exactly."""
    spec, params = lm
    rng = np.random.RandomState(7)
    # capacity 5 blocks of 8; span 18+6=24 -> 3 blocks per request, and
    # a reserve of 0: two concurrent requests would need 6 > 5.
    eng = PagedDecodeEngine(spec, params, slots=2, window=32,
                            block_size=8, num_blocks=6, chunk=4,
                            cache_prefixes=False)
    p1 = rng.randint(0, VOCAB, 18).astype(np.int32)
    p2 = rng.randint(0, VOCAB, 17).astype(np.int32)
    r1 = eng.submit(p1, 6)
    r2 = eng.submit(p2, 5)
    results = eng.run()
    np.testing.assert_array_equal(results[r1], _oracle(spec, params, p1, 6))
    np.testing.assert_array_equal(results[r2], _oracle(spec, params, p2, 5))
    assert eng.stats.deferred_blocks > 0
    eng.assert_no_leaks()

    # a pool that could never hold one full-window request is rejected
    # at construction (the invariant that makes deferral always
    # resolvable, never a livelock)
    with pytest.raises(ValueError, match="cannot hold"):
        PagedDecodeEngine(spec, params, slots=2, window=32,
                          block_size=8, num_blocks=4)


def test_paged_trie_eviction_under_pressure(lm):
    """Cached-but-unpinned prefix blocks are LRU-evicted when a new
    admission needs the room (the pool never deadlocks on its own
    cache)."""
    spec, params = lm
    rng = np.random.RandomState(8)
    # capacity 7: one 24-span request holds 3; its 2 cached prompt
    # blocks stay in the trie after completion (5 used at peak).
    eng = PagedDecodeEngine(spec, params, slots=1, window=32,
                            block_size=8, num_blocks=8, chunk=4)
    p1 = rng.randint(0, VOCAB, 18).astype(np.int32)
    r1 = eng.submit(p1, 6)
    results = eng.run()
    np.testing.assert_array_equal(results[r1], _oracle(spec, params, p1, 6))
    assert len(eng.trie) == 2
    # a second, unrelated max-size request needs 4 blocks: 5 free + 2
    # cached -> eviction must free at least one cached block
    p2 = rng.randint(0, VOCAB, 20).astype(np.int32)
    r2 = eng.submit(p2, 6)
    results = eng.run()
    np.testing.assert_array_equal(results[r2], _oracle(spec, params, p2, 6))
    eng.assert_no_leaks()


def test_paged_cancel_frees_blocks(lm):
    spec, params = lm
    rng = np.random.RandomState(9)
    eng = PagedDecodeEngine(spec, params, **GEOM, cache_prefixes=False)
    p1 = rng.randint(0, VOCAB, 3).astype(np.int32)
    p2 = rng.randint(0, VOCAB, 2).astype(np.int32)
    r1 = eng.submit(p1, 10)
    r2 = eng.submit(p2, 4)
    assert eng.step()
    used_mid = eng.pool.used_count
    assert used_mid > 0
    assert eng.cancel(r1)                 # in-flight: slot + blocks free
    assert not eng.cancel(r1)
    results = eng.run()
    assert sorted(results) == [r2]
    np.testing.assert_array_equal(results[r2], _oracle(spec, params, p2, 4))
    eng.assert_no_leaks()
    assert eng.pool.used_count == 0

    r3 = eng.submit(p1, 4)
    assert eng.cancel(r3)                 # still queued: no blocks held
    assert eng.pool.used_count == 0


def test_paged_eos_and_per_request_knobs(lm):
    """Per-request eos stops only its own request (eos kept, truncated
    after); a sampled request decodes alongside an exact greedy one."""
    spec, params = lm
    rng = np.random.RandomState(10)
    prompt = rng.randint(0, VOCAB, 4).astype(np.int32)
    free = _oracle(spec, params, prompt, 6)
    eos = int(free[prompt.size + 1])
    if eos == free[prompt.size]:  # pragma: no cover - degenerate repeat
        pytest.skip("greedy repeats a token; eos choice ambiguous")
    eng = PagedDecodeEngine(spec, params, **GEOM,
                            rng=jax.random.PRNGKey(7))
    r_stop = eng.submit(prompt, 6, eos_id=eos)
    r_sampled = eng.submit(prompt, 6, temperature=1.0)
    results = eng.run()
    np.testing.assert_array_equal(results[r_stop],
                                  free[:prompt.size + 2])
    assert results[r_stop][-1] == eos
    sampled = results[r_sampled]
    assert sampled.size == prompt.size + 6
    assert np.all((sampled >= 0) & (sampled < VOCAB))
    eng.assert_no_leaks()


def test_paged_set_prefix_compat(lm):
    """The set_prefix shim: use_prefix requests prepend the registered
    system prompt, dedup its K/V through the trie, and return only
    prompt+generated — exact vs the concat oracle."""
    spec, params = lm
    rng = np.random.RandomState(11)
    prefix = rng.randint(0, VOCAB, 9).astype(np.int32)   # 1 full block
    p1 = rng.randint(0, VOCAB, 3).astype(np.int32)
    p2 = rng.randint(0, VOCAB, 4).astype(np.int32)
    eng = PagedDecodeEngine(spec, params, **GEOM)
    assert eng.set_prefix(prefix) == 9
    r1 = eng.submit(p1, 5, use_prefix=True)
    out = eng.run()
    want1 = _oracle(spec, params, np.concatenate([prefix, p1]), 5)
    np.testing.assert_array_equal(out[r1], want1[prefix.size:])
    # second prefix request hits the cached block
    r2 = eng.submit(p2, 4, use_prefix=True)
    out = eng.run()
    want2 = _oracle(spec, params, np.concatenate([prefix, p2]), 4)
    np.testing.assert_array_equal(out[r2], want2[prefix.size:])
    assert eng.stats.cached_prompt_tokens == 8
    # clear_prefix: future plain submits unaffected, nothing freed that
    # the trie still caches
    eng.clear_prefix()
    with pytest.raises(ValueError, match="no prefix"):
        eng.submit(p1, 3, use_prefix=True)
    r3 = eng.submit(p1, 3)
    np.testing.assert_array_equal(eng.run()[r3],
                                  _oracle(spec, params, p1, 3))
    eng.assert_no_leaks()


def test_paged_pop_timings(lm):
    spec, params = lm
    rng = np.random.RandomState(12)
    eng = PagedDecodeEngine(spec, params, **GEOM)
    rid = eng.submit(rng.randint(0, VOCAB, 3).astype(np.int32), 5)
    eng.run()
    timings = eng.pop_timings()
    assert set(timings) == {rid}
    t = timings[rid]
    assert t["queue_wait_s"] >= 0
    assert t["ttft_s"] >= t["queue_wait_s"]
    assert t["generated"] == 5
    assert t["slo"] == SLO_LATENCY
    assert eng.pop_timings() == {}        # drained


def test_paged_validation(lm):
    spec, params = lm
    eng = PagedDecodeEngine(spec, params, slots=1, window=16,
                            block_size=8, num_blocks=8)
    with pytest.raises(ValueError, match="exceeds the engine"):
        eng.submit(np.arange(10, dtype=np.int32), 10)
    with pytest.raises(ValueError, match="at least one token"):
        eng.submit(np.zeros(0, np.int32), 2)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.arange(2, dtype=np.int32), 0)
    with pytest.raises(ValueError, match="out of vocab"):
        eng.submit(np.array([VOCAB + 3], np.int32), 2)
    with pytest.raises(ValueError, match="slo"):
        eng.submit(np.arange(2, dtype=np.int32), 2, slo="gold")
    with pytest.raises(ValueError, match="floor"):
        eng.submit(np.arange(2, dtype=np.int32), 2, temperature=1e-8)
    with pytest.raises(ValueError, match="rng"):
        eng.submit(np.arange(2, dtype=np.int32), 2, temperature=0.5)
    with pytest.raises(ValueError, match="multiple"):
        PagedDecodeEngine(spec, params, window=30, block_size=8)
    with pytest.raises(ValueError, match="max_len"):
        PagedDecodeEngine(spec, params, window=64, block_size=8)


@pytest.mark.slow
def test_paged_poisoned_after_failed_dispatch(lm, monkeypatch):
    import autodist_tpu.serving.scheduler as sched_mod

    spec, params = lm
    eng = PagedDecodeEngine(spec, params, **GEOM)
    eng.submit(np.arange(2, dtype=np.int32), 4)

    def boom(*a, **k):
        raise RuntimeError("device lost")

    monkeypatch.setattr(sched_mod, "_paged_prefill_program", boom)
    with pytest.raises(RuntimeError, match="device lost"):
        eng.run()
    monkeypatch.undo()
    with pytest.raises(RuntimeError, match="poisoned"):
        eng.step()
    with pytest.raises(RuntimeError, match="poisoned"):
        eng.submit(np.arange(2, dtype=np.int32), 2)
    eng.reset()
    prompt = np.arange(3, dtype=np.int32)
    rid = eng.submit(prompt, 4)
    np.testing.assert_array_equal(eng.run()[rid],
                                  _oracle(spec, params, prompt, 4))
    eng.assert_no_leaks()


@pytest.mark.slow
def test_paged_sustained_load_with_rebase(lm):
    """Steady stream over a small pool: tick rebases fire, blocks churn
    through many alloc/free cycles, every result stays exact, nothing
    leaks."""
    spec, params = lm
    rng = np.random.RandomState(13)
    eng = PagedDecodeEngine(spec, params, **GEOM)
    eng._REBASE_AT = 32
    ids, reqs, results = [], [], {}
    for _ in range(14):
        p = rng.randint(0, VOCAB, 3).astype(np.int32)
        reqs.append((p, 6))
        ids.append(eng.submit(p, 6))
        eng.step()
        results.update(eng.results())
    while eng.step():
        pass
    results.update(eng.results())
    for rid, (p, n) in zip(ids, reqs):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(spec, params, p, n))
    assert eng._tick < 32 + GEOM["window"] + GEOM["chunk"]
    eng.assert_no_leaks()


@pytest.mark.slow
def test_paged_mesh_sharded_pool(lm):
    """The mesh-sharded block pool: K/V pools sharded over the model
    (TP) axis — per-head attention has no cross-head math, so GSPMD
    runs each head group on its devices — oracle-exact, and donation
    keeps the sharding dispatch to dispatch."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    spec, params = lm
    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    rng = np.random.RandomState(16)
    reqs = [(rng.randint(0, VOCAB, p).astype(np.int32), n)
            for p, n in [(3, 5), (2, 6), (4, 4), (1, 7)]]
    eng = PagedDecodeEngine(spec, params, **GEOM, mesh=mesh)
    ids = [eng.submit(p, n) for p, n in reqs]
    results = eng.run()
    for rid, (prompt, n) in zip(ids, reqs):
        np.testing.assert_array_equal(results[rid],
                                      _oracle(spec, params, prompt, n))
    want = NamedSharding(mesh, PartitionSpec(None, None, None, "model"))
    assert eng._kc.sharding.is_equivalent_to(want, eng._kc.ndim)
    assert eng._vc.sharding.is_equivalent_to(want, eng._vc.ndim)
    eng.assert_no_leaks()

    with pytest.raises(ValueError, match="not in mesh axes"):
        PagedDecodeEngine(spec, params, **GEOM, mesh=mesh,
                          model_axis="data")


@pytest.mark.slow
def test_paged_quantized_params(lm):
    """Weight-only int8 trees route through the same paged programs."""
    from autodist_tpu.models.quantize import quantize_lm_params

    spec, params = lm
    qp = quantize_lm_params(params)
    rng = np.random.RandomState(14)
    gen = make_generator(spec)
    reqs = [(rng.randint(0, VOCAB, p).astype(np.int32), n)
            for p, n in [(3, 4), (2, 6), (5, 3)]]
    eng = PagedDecodeEngine(spec, qp, **GEOM)
    ids = [eng.submit(p, n) for p, n in reqs]
    results = eng.run()
    for rid, (prompt, n) in zip(ids, reqs):
        want = np.asarray(gen(qp, prompt[None, :], n))[0]
        np.testing.assert_array_equal(results[rid], want)
    eng.assert_no_leaks()


# ---------------------------------------------------------------------------
# slot-engine satellites: bounded queue + mid-flight prefix pinning
# ---------------------------------------------------------------------------

def test_slot_engine_bounded_queue(lm):
    from autodist_tpu.serving import DecodeEngine

    spec, params = lm
    eng = DecodeEngine(spec, params, slots=1, window=16, chunk=2,
                       max_queue=2)
    eng.submit(np.arange(2, dtype=np.int32), 3)
    eng.submit(np.arange(2, dtype=np.int32), 3)
    with pytest.raises(AdmissionError) as exc:
        eng.submit(np.arange(2, dtype=np.int32), 3)
    assert exc.value.retry_after_s > 0
    eng.run()
    # queue drained: submits admit again
    eng.submit(np.arange(2, dtype=np.int32), 3)
    eng.run()


def test_slot_engine_mid_flight_prefix_swap_pins_readers(lm):
    """set_prefix mid-flight: admitted requests keep decoding against
    the generation they pinned (exact vs the OLD-prefix oracle), later
    submits use the new one — the stale-prefix-KV bug is closed by
    per-request pinning, not by requiring an idle engine."""
    from autodist_tpu.serving import DecodeEngine

    spec, params = lm
    rng = np.random.RandomState(15)
    old = rng.randint(0, VOCAB, 5).astype(np.int32)
    new = rng.randint(0, VOCAB, 7).astype(np.int32)
    p1 = rng.randint(0, VOCAB, 3).astype(np.int32)
    p2 = rng.randint(0, VOCAB, 2).astype(np.int32)

    eng = DecodeEngine(spec, params, slots=2, window=24, chunk=2)
    eng.set_prefix(old)
    r_old = eng.submit(p1, 8, use_prefix=True)
    assert eng.step()                       # r_old decoding against OLD
    eng.set_prefix(new)                     # swap mid-flight: allowed now
    r_new = eng.submit(p2, 5, use_prefix=True)
    while eng.step():
        pass
    results = eng.results()
    want_old = _oracle(spec, params, np.concatenate([old, p1]), 8)
    np.testing.assert_array_equal(results[r_old], want_old[old.size:],
                                  err_msg="in-flight reader lost its "
                                          "pinned prefix")
    want_new = _oracle(spec, params, np.concatenate([new, p2]), 5)
    np.testing.assert_array_equal(results[r_new], want_new[new.size:])

    # clear_prefix mid-flight: the reader keeps its pin to the end
    eng.set_prefix(old)
    r3 = eng.submit(p1, 6, use_prefix=True)
    assert eng.step()
    eng.clear_prefix()
    with pytest.raises(ValueError, match="no prefix"):
        eng.submit(p2, 3, use_prefix=True)
    while eng.step():
        pass
    out3 = eng.results()[r3]
    np.testing.assert_array_equal(
        out3, _oracle(spec, params, np.concatenate([old, p1]), 6)[old.size:])
