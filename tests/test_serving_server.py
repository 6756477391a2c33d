"""EngineServer: the HTTP front over DecodeEngine.

Token-exactness through the network boundary (concurrent requests vs
the per-request oracle), SSE streaming (deltas reassemble to the final
result), cancel, stats, tokenizer text mode, and error paths — all on
the CPU backend with a tiny model, real sockets on localhost.
"""
import http.client
import json
import threading

import jax
import numpy as np
import pytest

from autodist_tpu.models.generate import make_generator
from autodist_tpu.models.transformer import dense_attention
from autodist_tpu.models.transformer_lm import transformer_lm
from autodist_tpu.serving import DecodeEngine, EngineServer

VOCAB = 61


@pytest.fixture(scope="module")
def lm():
    spec = transformer_lm(vocab_size=VOCAB, num_layers=2, num_heads=2,
                          head_dim=8, d_ff=32, max_len=48, seq_len=16,
                          attn_fn=dense_attention)
    params = spec.init(jax.random.PRNGKey(0))
    return spec, params


@pytest.fixture()
def server(lm):
    spec, params = lm
    eng = DecodeEngine(spec, params, slots=2, window=24, chunk=4)
    srv = EngineServer(eng, port=0, request_timeout_s=120).start()
    yield srv
    srv.close()


def _post(addr, path, body, timeout=120):
    conn = http.client.HTTPConnection(*addr, timeout=timeout)
    conn.request("POST", path, json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    out = resp.status, json.loads(resp.read())
    conn.close()
    return out


def _get(addr, path):
    conn = http.client.HTTPConnection(*addr, timeout=30)
    conn.request("GET", path)
    resp = conn.getresponse()
    out = resp.status, json.loads(resp.read())
    conn.close()
    return out


def test_completions_token_exact_concurrent(server, lm):
    """More concurrent requests than engine slots, served over HTTP:
    each response equals the per-request oracle decode."""
    spec, params = lm
    gen = make_generator(spec)
    rng = np.random.RandomState(0)
    reqs = [(rng.randint(0, VOCAB, p).tolist(), n)
            for p, n in [(3, 5), (1, 8), (5, 3), (2, 6), (4, 4)]]
    out = {}

    def issue(i, prompt, n):
        out[i] = _post(server.address, "/v1/completions",
                       {"prompt_tokens": prompt, "max_new_tokens": n})

    threads = [threading.Thread(target=issue, args=(i, p, n))
               for i, (p, n) in enumerate(reqs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    for i, (prompt, n) in enumerate(reqs):
        status, body = out[i]
        assert status == 200, body
        want = np.asarray(gen(
            params, np.asarray(prompt, np.int32)[None, :], n))[0]
        np.testing.assert_array_equal(body["tokens"], want)
        assert body["new_tokens"] == body["tokens"][len(prompt):]
        assert len(body["new_tokens"]) == n

    status, st = _get(server.address, "/v1/stats")
    assert status == 200
    assert st["requests_served"] == len(reqs)
    assert st["completed"] == len(reqs)
    assert st["outstanding"] == 0
    assert not st["engine_failed"]


def test_streaming_deltas_reassemble(lm):
    """SSE stream: non-final events carry monotone new-token deltas that
    concatenate exactly to the final result's new_tokens.  The engine's
    step is throttled so chunk boundaries are strictly slower than the
    handler's poll cadence — deltas MUST surface (a tiny CPU decode can
    otherwise finish between two polls)."""
    import time as _time

    spec, params = lm
    gen = make_generator(spec)
    prompt = [7, 3, 11]
    n = 9

    eng = DecodeEngine(spec, params, slots=2, window=24, chunk=2)
    orig_step = eng.step
    eng.step = lambda: (_time.sleep(0.08), orig_step())[1]
    srv = EngineServer(eng, port=0, request_timeout_s=120).start()
    conn = http.client.HTTPConnection(*srv.address, timeout=120)
    conn.request("POST", "/v1/completions",
                 json.dumps({"prompt_tokens": prompt, "max_new_tokens": n,
                             "stream": True}),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.getheader("Content-Type") == "text/event-stream"
    events = []
    while True:
        line = resp.readline()
        if not line:
            break
        line = line.strip()
        if line.startswith(b"data: "):
            events.append(json.loads(line[len(b"data: "):]))
            if events[-1].get("done"):
                break
    conn.close()
    srv.close()

    assert events and events[-1]["done"]
    final = events[-1]
    want = np.asarray(gen(
        params, np.asarray(prompt, np.int32)[None, :], n))[0]
    np.testing.assert_array_equal(final["tokens"], want)
    deltas = [t for ev in events[:-1] for t in ev["new_tokens"]]
    # Deltas surface at chunk boundaries; together they are a prefix of
    # (possibly all of) the generated tokens, in order.
    assert deltas == final["new_tokens"][:len(deltas)]
    assert len(deltas) > 0   # something streamed before completion


def test_tokenizer_text_mode(lm):
    """With a tokenizer installed, 'prompt' strings round-trip and the
    response carries decoded text."""
    spec, params = lm

    class Toy:
        def encode(self, s):
            return [ord(c) % VOCAB for c in s]

        def decode(self, toks):
            return "".join(chr(97 + (t % 26)) for t in toks)

    eng = DecodeEngine(spec, params, slots=1, window=24, chunk=4)
    with EngineServer(eng, port=0, tokenizer=Toy(),
                      request_timeout_s=120) as srv:
        status, body = _post(srv.address, "/v1/completions",
                             {"prompt": "hi", "max_new_tokens": 3})
        assert status == 200, body
        assert isinstance(body["text"], str)
        assert len(body["text"]) == len(body["tokens"])
        assert len(body["new_tokens"]) == 3


def test_validation_and_unknown_paths(server):
    addr = server.address
    # over-window request → engine ValueError → 400 with the message
    status, body = _post(addr, "/v1/completions",
                         {"prompt_tokens": [1] * 20,
                          "max_new_tokens": 20})
    assert status == 400 and "window" in body["error"]
    status, body = _post(addr, "/v1/completions",
                         {"max_new_tokens": 4})
    assert status == 400 and "prompt_tokens" in body["error"]
    # text prompt without a tokenizer is rejected loudly
    status, body = _post(addr, "/v1/completions",
                         {"prompt": "hello", "max_new_tokens": 4})
    assert status == 400 and "tokenizer" in body["error"]
    status, body = _post(addr, "/v1/completions",
                         {"prompt_tokens": [1, 2], "max_new_tokens": "x"})
    assert status == 400
    status, _ = _post(addr, "/v1/nope", {})
    assert status == 404
    status, _ = _get(addr, "/v1/nope")
    assert status == 404
    status, body = _get(addr, "/healthz")
    assert status == 200 and body["ok"]


def test_late_submit_joins_running_batch(lm):
    """Continuous batching THROUGH the HTTP boundary: a short request
    submitted while a long one is mid-decode joins the running batch
    and finishes first.  Guards the driver-loop lock release — holding
    the lock across the busy loop would serialize the server into one
    batch per drain (the short request would then finish last)."""
    import time as _time

    spec, params = lm
    # Wide margin for loaded CI hosts: the long request holds ~24
    # throttled chunks (>1 s) after the short one lands, while the short
    # one needs ~2 — ordering survives coarse thread scheduling.
    eng = DecodeEngine(spec, params, slots=2, window=48, chunk=2)
    orig_step = eng.step
    eng.step = lambda: (_time.sleep(0.05), orig_step())[1]
    done_order = []

    def issue(tag, n):
        status, body = _post(srv.address, "/v1/completions",
                             {"prompt_tokens": [3, 5], "max_new_tokens": n})
        assert status == 200, body
        done_order.append(tag)

    with EngineServer(eng, port=0, request_timeout_s=120) as srv:
        t_long = threading.Thread(target=issue, args=("long", 46))
        t_long.start()
        _time.sleep(0.4)    # several throttled chunks into the long decode
        t_short = threading.Thread(target=issue, args=("short", 2))
        t_short.start()
        t_long.join()
        t_short.join()
    assert done_order == ["short", "long"]


def test_timeout_cancels_and_frees_the_slot(lm):
    """A request outliving request_timeout_s answers 504 and is
    cancelled (slot freed): a follow-up request still completes."""
    import time as _time

    spec, params = lm
    eng = DecodeEngine(spec, params, slots=1, window=24, chunk=2)
    orig_step = eng.step
    eng.step = lambda: (_time.sleep(0.05), orig_step())[1]
    with EngineServer(eng, port=0, request_timeout_s=0.3) as srv:
        status, body = _post(srv.address, "/v1/completions",
                             {"prompt_tokens": [1, 2],
                              "max_new_tokens": 20})
        assert status == 504 and "cancelled" in body["error"]
        eng.step = orig_step   # un-throttle; the slot must be free
        status, body = _post(srv.address, "/v1/completions",
                             {"prompt_tokens": [4], "max_new_tokens": 2})
        assert status == 200, body
        assert len(body["new_tokens"]) == 2


def test_cancel_unknown_and_queued(server):
    addr = server.address
    # unknown id
    status, body = _post(addr, "/v1/cancel", {"id": 12345})
    assert status == 200 and body["cancelled"] is False
    status, body = _post(addr, "/v1/cancel", {"id": "x"})
    assert status == 400


def test_backpressure_answers_429_with_retry_after(lm):
    """A full engine queue surfaces the typed AdmissionError as HTTP
    429 with a Retry-After header — the bounded-queue satellite.

    Each request is sent when the engine SHOWS the state the one before it
    was to bring about (the slot taken, then the queue full), not after a
    fixed sleep: under the suite's six workers the first step's compile
    alone outlasts any sleep short enough to keep, and the third request
    then found the slot free or the queue empty."""
    import time as _time

    def until(state, what, timeout_s=120.0):
        deadline = _time.monotonic() + timeout_s
        while not state():
            assert _time.monotonic() < deadline, f"never saw: {what}"
            _time.sleep(0.01)

    spec, params = lm
    eng = DecodeEngine(spec, params, slots=1, window=24, chunk=2,
                       max_queue=1)
    orig_step = eng.step
    eng.step = lambda: (_time.sleep(0.2), orig_step())[1]
    with EngineServer(eng, port=0, request_timeout_s=120) as srv:
        t1 = threading.Thread(
            target=_post, args=(srv.address, "/v1/completions",
                                {"prompt_tokens": [1, 2],
                                 "max_new_tokens": 16}))
        t1.start()
        until(lambda: any(eng._active) and not eng._queue,
              "the first request in the slot, the queue empty")
        t2 = threading.Thread(
            target=_post, args=(srv.address, "/v1/completions",
                                {"prompt_tokens": [3],
                                 "max_new_tokens": 8}))
        t2.start()
        until(lambda: len(eng._queue) == 1, "the second request queued")
        conn = http.client.HTTPConnection(*srv.address, timeout=30)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt_tokens": [4],
                                 "max_new_tokens": 2}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        retry_hdr = resp.getheader("Retry-After")
        conn.close()
        assert resp.status == 429, body
        assert "retry" in body["error"].lower() or "full" in body["error"]
        assert body["retry_after_s"] > 0
        assert retry_hdr is not None and int(retry_hdr) >= 1
        eng.step = orig_step
        t1.join()
        t2.join()
    st = srv.stats()
    assert st["requests_failed"] >= 1        # the 429 counted as failed


@pytest.fixture()
def paged_server(lm):
    from autodist_tpu.serving import PagedDecodeEngine

    spec, params = lm
    eng = PagedDecodeEngine(spec, params, slots=2, window=32,
                            block_size=8, num_blocks=24, chunk=4)
    srv = EngineServer(eng, port=0, request_timeout_s=120).start()
    yield srv
    srv.close()


def test_paged_engine_through_http(paged_server, lm):
    """The paged scheduler behind the HTTP front: oracle-exact
    completions, SLO class accepted, scheduler surface in /v1/stats,
    serving gauges + TTFT histogram on /metrics."""
    spec, params = lm
    gen = make_generator(spec)
    addr = paged_server.address
    status, body = _post(addr, "/v1/completions",
                         {"prompt_tokens": [3, 5, 7], "max_new_tokens": 5,
                          "slo": "throughput"})
    assert status == 200, body
    want = np.asarray(gen(
        params, np.asarray([3, 5, 7], np.int32)[None, :], 5))[0]
    np.testing.assert_array_equal(body["tokens"], want)

    status, body = _post(addr, "/v1/completions",
                         {"prompt_tokens": [1], "max_new_tokens": 2,
                          "slo": "gold"})
    assert status == 400 and "slo" in body["error"]

    status, st = _get(addr, "/v1/stats")
    assert status == 200
    assert st["queue_depth"] == {"latency": 0, "throughput": 0}
    assert st["block_occupancy"] >= 0
    assert "prefix_hit_rate" in st and "free_blocks" in st
    assert st["ttft_p50_ms"] > 0

    conn = http.client.HTTPConnection(*addr, timeout=30)
    conn.request("GET", "/metrics")
    resp = conn.getresponse()
    text = resp.read().decode()
    conn.close()
    assert "autodist_serving_ttft_seconds_bucket" in text
    assert "autodist_serving_queue_wait_seconds_bucket" in text
    assert "autodist_serving_block_occupancy" in text
    assert 'autodist_serving_queue_depth_class{slo="latency"}' in text


def test_slot_engine_rejects_slo_field(server):
    status, body = _post(server.address, "/v1/completions",
                         {"prompt_tokens": [1, 2], "max_new_tokens": 2,
                          "slo": "latency"})
    assert status == 400 and "SLO" in body["error"]
