"""Speculative decoding in the paged serving plane.

The claim under test is the serving engine's own claim — token-exact
greedy decode vs the per-request ``generate`` oracle — carried into
speculative mode: draft K/V paged out of the SAME block pool, the
target verifying gamma+1 positions per round on the chunked-prefill
program, and SLO-adaptive gamma.  Acceptance may vary with the draft's
quality; the OUTPUT may not.  Every scheduler feature that interacts
with the dual block spans gets a case: prefix-cache hits, chunked
prefill, mid-run admission, block-budget deferral under pool pressure,
eos cut-off, per-request gamma, and the no-leak invariant over the
draft tables.
"""
import jax
import numpy as np
import pytest

from autodist_tpu.models.generate import make_generator
from autodist_tpu.models.transformer import dense_attention
from autodist_tpu.models.transformer_lm import transformer_lm
from autodist_tpu.serving import PagedDecodeEngine

pytestmark = [pytest.mark.serving, pytest.mark.spec_serving]

VOCAB = 61
# Same target geometry as test_serving_scheduler so the paged programs
# come out of the module-scope jit cache already compiled.
GEOM = dict(slots=2, window=32, block_size=8, num_blocks=24, chunk=4)


@pytest.fixture(scope="module")
def lm():
    spec = transformer_lm(vocab_size=VOCAB, num_layers=2, num_heads=2,
                          head_dim=8, d_ff=32, max_len=48, seq_len=16,
                          attn_fn=dense_attention)
    return spec, spec.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def draft():
    # Different depth AND different init: a draft that genuinely
    # disagrees with the target (low acceptance), so every exactness
    # assertion exercises the reject-and-bonus path, not just accepts.
    spec = transformer_lm(vocab_size=VOCAB, num_layers=1, num_heads=2,
                          head_dim=8, d_ff=32, max_len=48, seq_len=16,
                          attn_fn=dense_attention)
    return spec, spec.init(jax.random.PRNGKey(9))


def _spec_engine(lm, draft, **over):
    spec, params = lm
    dspec, dparams = draft
    kw = dict(GEOM)
    kw.update(over)
    return PagedDecodeEngine(spec, params, draft_spec=dspec,
                             draft_params=dparams, **kw)


def _oracle(spec, params, prompt, n):
    return np.asarray(make_generator(spec)(params, prompt[None, :], n))[0]


@pytest.mark.parametrize(
    "gamma", [pytest.param(1, marks=pytest.mark.slow), 4])
def test_spec_matches_oracle_exactly(lm, draft, gamma):
    """More requests than slots, varied prompt/output lengths, a bad
    draft: every harvested sequence equals the target-only oracle and
    both block spans recycle."""
    spec, params = lm
    rng = np.random.RandomState(1)
    reqs = [(rng.randint(0, VOCAB, p).astype(np.int32), n)
            for p, n in [(3, 5), (1, 9), (6, 2), (4, 7), (2, 4)]]
    eng = _spec_engine(lm, draft, gamma=gamma, adapt_gamma=False)
    ids = [eng.submit(p, n) for p, n in reqs]
    results = eng.run()
    for rid, (prompt, n) in zip(ids, reqs):
        np.testing.assert_array_equal(
            results[rid], _oracle(spec, params, prompt, n),
            err_msg=f"request {rid} (P={prompt.size}, N={n}, g={gamma})")
    sp = eng.scheduler_stats()["speculative"]
    assert sp["rounds"] > 0 and sp["proposed"] >= sp["accepted"] >= 0
    eng.assert_no_leaks()


def test_spec_mid_run_admission_exact(lm, draft):
    """Requests admitted WHILE speculative rounds run: the draft
    catch-up prefill and the dual-span admission must not disturb
    in-flight slots."""
    spec, params = lm
    rng = np.random.RandomState(4)
    eng = _spec_engine(lm, draft, gamma=3, adapt_gamma=False)
    p1 = rng.randint(0, VOCAB, 3).astype(np.int32)
    p2 = rng.randint(0, VOCAB, 2).astype(np.int32)
    p3 = rng.randint(0, VOCAB, 5).astype(np.int32)
    r1 = eng.submit(p1, 6)
    assert eng.step()
    r2 = eng.submit(p2, 5)            # joins mid-speculation
    eng.step()
    r3 = eng.submit(p3, 4)
    while eng.step():
        pass
    results = eng.results()
    np.testing.assert_array_equal(results[r1], _oracle(spec, params, p1, 6))
    np.testing.assert_array_equal(results[r2], _oracle(spec, params, p2, 5))
    np.testing.assert_array_equal(results[r3], _oracle(spec, params, p3, 4))
    eng.assert_no_leaks()


def test_spec_chunked_prefill_exact(lm, draft):
    """prefill_chunk smaller than the prompt: target and draft prefill
    walk the prompt in separate chunk waves (the draft lags by design)
    and the verify rounds still start from a consistent K/V."""
    spec, params = lm
    rng = np.random.RandomState(5)
    eng = _spec_engine(lm, draft, gamma=3, adapt_gamma=False,
                       prefill_chunk=3)
    reqs = [(rng.randint(0, VOCAB, p).astype(np.int32), n)
            for p, n in [(11, 5), (7, 6), (13, 4)]]
    ids = [eng.submit(p, n) for p, n in reqs]
    results = eng.run()
    for rid, (prompt, n) in zip(ids, reqs):
        np.testing.assert_array_equal(
            results[rid], _oracle(spec, params, prompt, n))
    eng.assert_no_leaks()


@pytest.mark.slow
def test_spec_prefix_cache_hit_exact(lm, draft):
    """Trie-cached prompt blocks serve the TARGET span only — the
    draft has no trie, so its catch-up prefill must rebuild draft K/V
    over the cached tokens too.  Exact output plus a real cache hit."""
    spec, params = lm
    rng = np.random.RandomState(2)
    shared = rng.randint(0, VOCAB, 17).astype(np.int32)   # 2 full blocks
    prompts = [np.concatenate([shared,
                               rng.randint(0, VOCAB, 3).astype(np.int32)])
               for _ in range(3)]
    eng = _spec_engine(lm, draft, gamma=3, adapt_gamma=False,
                       num_blocks=40)
    r0 = eng.submit(prompts[0], 5)                        # warms the trie
    out = eng.run()
    np.testing.assert_array_equal(out[r0],
                                  _oracle(spec, params, prompts[0], 5))
    ids = [eng.submit(p, 6) for p in prompts[1:]]
    out = eng.run()
    for rid, p in zip(ids, prompts[1:]):
        np.testing.assert_array_equal(out[rid],
                                      _oracle(spec, params, p, 6))
    assert eng.stats.cached_prompt_tokens > 0
    eng.assert_no_leaks()


@pytest.mark.slow
def test_spec_pool_pressure_deferral_exact(lm, draft):
    """A pool barely larger than one dual span: admission must defer
    (not deadlock, not leak) until blocks recycle, and the deferred
    requests still come out exact."""
    spec, params = lm
    rng = np.random.RandomState(6)
    # capacity 11 blocks; a (P=9, N=7) request spans 2 target + 2 draft
    # blocks at admission and grows to 4+4 — two in flight exhaust it.
    eng = _spec_engine(lm, draft, gamma=3, adapt_gamma=False,
                       num_blocks=12, cache_prefixes=False)
    reqs = [(rng.randint(0, VOCAB, 9).astype(np.int32), 7)
            for _ in range(3)]
    ids = [eng.submit(p, n) for p, n in reqs]
    results = eng.run()
    for rid, (prompt, n) in zip(ids, reqs):
        np.testing.assert_array_equal(
            results[rid], _oracle(spec, params, prompt, n))
    eng.assert_no_leaks()


def test_spec_eos_matches_plain_paged(lm, draft):
    """eos cut-off parity: the speculative engine truncates at the
    first eos exactly where the non-speculative paged engine does —
    committed tokens only, never an un-verified proposal."""
    spec, params = lm
    rng = np.random.RandomState(7)
    prompt = rng.randint(0, VOCAB, 4).astype(np.int32)
    free = _oracle(spec, params, prompt, 8)
    eos = int(free[prompt.size + 1])      # fires mid-generation
    plain = PagedDecodeEngine(spec, params, **GEOM)
    rp = plain.submit(prompt, 8, eos_id=eos)
    expected = plain.run()[rp]
    eng = _spec_engine(lm, draft, gamma=4, adapt_gamma=False)
    rs = eng.submit(prompt, 8, eos_id=eos)
    got = eng.run()[rs]
    np.testing.assert_array_equal(got, expected)
    eng.assert_no_leaks()


def test_spec_per_request_gamma_exact(lm, draft):
    """submit(gamma=1) pins one request to single-proposal rounds while
    its neighbor drafts at the engine depth — per-slot ``ge`` vectors,
    one shared program."""
    spec, params = lm
    rng = np.random.RandomState(8)
    p1 = rng.randint(0, VOCAB, 4).astype(np.int32)
    p2 = rng.randint(0, VOCAB, 6).astype(np.int32)
    eng = _spec_engine(lm, draft, gamma=4, adapt_gamma=False)
    r1 = eng.submit(p1, 7, gamma=1)
    r2 = eng.submit(p2, 7)
    results = eng.run()
    np.testing.assert_array_equal(results[r1], _oracle(spec, params, p1, 7))
    np.testing.assert_array_equal(results[r2], _oracle(spec, params, p2, 7))
    eng.assert_no_leaks()


@pytest.mark.parametrize(
    "good_draft", [pytest.param(False, marks=pytest.mark.slow), True])
def test_spec_gamma_adapts_mid_flight(lm, draft, good_draft):
    """SLO adaptation under backlog: a burst beyond the slot count
    shrinks gamma (latency queue pressure); after the drain (idle slot,
    empty queue) a GOOD draft's gamma regrows, while a bad draft's
    acceptance EWMA caps it — all without breaking exactness."""
    spec, params = lm
    rng = np.random.RandomState(9)
    # the last request outlives the rest: the tail has an idle slot
    reqs = [(rng.randint(0, VOCAB, 4).astype(np.int32), n)
            for n in (8, 8, 8, 8, 8, 8, 8, 20)]
    # the good draft IS the target: every proposal is accepted
    eng = _spec_engine(lm, lm if good_draft else draft, gamma=6,
                       adapt_gamma=True)
    ids = [eng.submit(p, n) for p, n in reqs]     # 8 requests, 2 slots
    trace = []
    while eng.step():
        trace.append(eng.scheduler_stats()["speculative"]["gamma"])
    results = eng.results()
    for rid, (prompt, n) in zip(ids, reqs):
        np.testing.assert_array_equal(
            results[rid], _oracle(spec, params, prompt, n))
    assert min(trace) < 6, f"gamma never shrank under backlog: {trace}"
    sp = eng.scheduler_stats()["speculative"]
    if good_draft:
        assert trace[-1] > min(trace), \
            f"gamma never regrew after the drain: {trace}"
    else:
        # The tail wants to regrow gamma, but a bad draft's acceptance
        # EWMA caps it — degradation toward plain decode wins over the
        # utilization signal.
        assert sp["accept_ewma"] < 6.0
        cap = max(1, int(round(2 * sp["accept_ewma"])))
        assert trace[-1] <= min(6, cap), \
            f"tail gamma {trace[-1]} exceeds the EWMA cap {cap}"
    assert len(sp["gamma_hist"]) > 1      # adaptation actually moved
    eng.assert_no_leaks()


def test_spec_occupancy_split_and_timings(lm, draft):
    """The observability surface: scheduler_stats splits occupancy
    into target vs draft while in flight (draft > 0) and back to zero
    after the drain; pop_timings carries the per-request speculation
    fields the server histograms."""
    spec, params = lm
    rng = np.random.RandomState(10)
    eng = _spec_engine(lm, draft, gamma=3, adapt_gamma=False)
    rid = eng.submit(rng.randint(0, VOCAB, 6).astype(np.int32), 6)
    eng.step()
    eng.step()
    st = eng.scheduler_stats()
    assert st["draft_blocks_used"] > 0
    assert st["block_occupancy_draft"] > 0
    assert st["block_occupancy_target"] > 0
    while eng.step():
        pass
    eng.results()
    t = eng.pop_timings()[rid]
    assert t["spec_rounds"] >= 1
    assert t["spec_proposed"] >= t["spec_accepted"] >= 0
    assert t["spec_bonus"] >= 1           # every round commits >= 1
    assert t["accept_len_mean"] >= 0.0
    assert t["draft_s"] >= 0.0 and t["verify_s"] >= 0.0
    st = eng.scheduler_stats()
    assert st["draft_blocks_used"] == 0
    assert st["block_occupancy_draft"] == 0.0
    eng.assert_no_leaks()


def test_spec_submit_validation(lm, draft):
    """Knobs that would fail mid-run are rejected at submit/construct
    time: gamma < 1, non-greedy temperature, span + gamma overflowing
    the window, and per-request gamma on a non-speculative engine."""
    spec, params = lm
    dspec, dparams = draft
    prompt = np.zeros(4, np.int32)
    with pytest.raises(ValueError, match="gamma"):
        _spec_engine(lm, draft, gamma=0)
    with pytest.raises(ValueError, match="temperature|greedy"):
        _spec_engine(lm, draft, gamma=2, temperature=0.7)
    eng = _spec_engine(lm, draft, gamma=2, adapt_gamma=False)
    with pytest.raises(ValueError, match="gamma"):
        eng.submit(prompt, 5, gamma=0)
    with pytest.raises(ValueError, match="temperature|greedy"):
        eng.submit(prompt, 5, temperature=0.7)
    with pytest.raises(ValueError, match="window"):
        # span 4+26 = 30 fits the window 32, but not plus gamma 4.
        eng.submit(prompt, 26, gamma=4)
    plain = PagedDecodeEngine(spec, params, **GEOM)
    with pytest.raises(ValueError, match="speculative engine"):
        plain.submit(prompt, 5, gamma=2)
    with pytest.raises(ValueError, match="together"):
        PagedDecodeEngine(spec, params, draft_spec=dspec, **GEOM)


def test_router_weighs_draft_occupancy():
    """A mixed fleet: with draft_occupancy_weight set, the router
    steers away from the replica whose pool is loaded with draft
    pages, all else equal; with the default weight 0 the split is
    invisible (backward-compatible scoring)."""
    from autodist_tpu.serving.router import Router

    class FakeReplica:
        def __init__(self, name, draft_occ):
            self.name = name
            self.draft_occ = draft_occ
            self.served = []

        def probe(self, timeout=2.0):
            return True

        def fetch_stats(self):
            return {"outstanding": 0, "queue_depth_total": 0,
                    "block_occupancy": 0.5,
                    "block_occupancy_draft": self.draft_occ}

        def post(self, body, timeout):
            self.served.append(body)
            return 200, {"id": len(self.served), "tokens": [1]}

    a, b = FakeReplica("a", 0.4), FakeReplica("b", 0.0)
    r = Router([a, b], probe_ttl_s=0.0, stats_ttl_s=0.0,
               draft_occupancy_weight=2.0)
    for _ in range(3):
        r.complete({"prompt_tokens": [1], "max_new_tokens": 2})
    assert len(b.served) == 3 and len(a.served) == 0


def test_spec_http_server_surface(lm, draft):
    """serve(speculative=...) end to end: a token-exact completion
    with a per-request gamma, the spec block on /v1/stats, the spec
    metrics on /metrics, and fail-fast 400 on a bad gamma."""
    import json
    import urllib.error
    import urllib.request

    from autodist_tpu.serving import serve

    spec, params = lm
    dspec, dparams = draft
    srv = serve(spec, params, port=0, slots=2, window=32, block_size=8,
                num_blocks=24, chunk=4,
                speculative={"spec": dspec, "params": dparams,
                             "gamma": 3, "adapt_gamma": True})
    try:
        port = srv.address[1]
        base = f"http://127.0.0.1:{port}"
        prompt = np.random.RandomState(3).randint(0, VOCAB, 5)
        body = json.dumps({"prompt_tokens": [int(x) for x in prompt],
                           "max_new_tokens": 6, "gamma": 2}).encode()
        req = urllib.request.Request(
            base + "/v1/completions", data=body,
            headers={"Content-Type": "application/json"})
        out = json.loads(urllib.request.urlopen(req, timeout=120).read())
        np.testing.assert_array_equal(
            np.asarray(out["tokens"]),
            _oracle(spec, params, prompt.astype(np.int32), 6))
        stats = json.loads(urllib.request.urlopen(
            base + "/v1/stats", timeout=30).read())
        assert "speculative" in stats
        assert stats["speculative"]["rounds"] >= 1
        assert "block_occupancy_draft" in stats
        mets = urllib.request.urlopen(
            base + "/metrics", timeout=30).read().decode()
        for name in ("autodist_serving_spec_accept_len",
                     "autodist_serving_spec_gamma",
                     "autodist_serving_spec_gamma_current",
                     "autodist_serving_block_occupancy_target",
                     "autodist_serving_block_occupancy_draft"):
            assert name in mets, f"missing {name} on /metrics"
        bad = json.dumps({"prompt_tokens": [1, 2], "max_new_tokens": 4,
                          "gamma": 0}).encode()
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(urllib.request.Request(
                base + "/v1/completions", data=bad,
                headers={"Content-Type": "application/json"}),
                timeout=30)
        assert err.value.code == 400
    finally:
        srv.close()


@pytest.mark.slow
def test_spec_sustained_load_drill(lm, draft):
    """Long mixed drill: 16 requests arriving in waves over 2 slots
    with adaptation on — sustained slot/block recycling across many
    draft spans, exact throughout, nothing leaked at the end."""
    spec, params = lm
    rng = np.random.RandomState(11)
    reqs = [(rng.randint(0, VOCAB, int(rng.randint(1, 10))).astype(
        np.int32), int(rng.randint(2, 10))) for _ in range(16)]
    eng = _spec_engine(lm, draft, gamma=4, adapt_gamma=True)
    pending = list(reqs)
    ids = []
    while pending:
        for p, n in pending[:3]:
            ids.append(eng.submit(p, n))
        pending = pending[3:]
        eng.step()
    while eng.step():
        pass
    results = eng.results()
    for rid, (prompt, n) in zip(ids, reqs):
        np.testing.assert_array_equal(
            results[rid], _oracle(spec, params, prompt, n),
            err_msg=f"request {rid} (P={prompt.size}, N={n})")
    eng.assert_no_leaks()
