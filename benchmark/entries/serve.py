"""Entry ``serve``: ``serve(paged=True)`` over HTTP, streamed, greedy.

The parent (this process) holds the chip: it makes the weights, starts
the server, warms every program the mix can form, and watches.  The load
comes from a child process (``benchmark/loadgen.py``) that never imports
JAX.  Once the window has closed and the engine has drained, the server
is shut, its state freed, and a seeded sample of the requests the window
finished is run through the reference.
"""
from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmark.harness import memory_peak_bytes, percentile


# ---------------------------------------------------------------------------
# which programs the mix can form
# ---------------------------------------------------------------------------

def _pow2_bucket(n: int, cap: int) -> int:
    """The engine's own compile bucket (serving/scheduler.py): next
    power of two, or the exact size past the cap."""
    pb = 1 << (n - 1).bit_length()
    return pb if pb <= cap else n


def prefill_shapes(mix: dict, engine: dict) -> list:
    """Every (rows, bucket) prefill dispatch the engine can form for this
    mix: rows are powers of two up to ``slots``; the bucket is that of a
    chunk's length.  A prompt of L tokens is charged whole (no
    ``prefill_chunk``) or in pieces of ``prefill_chunk`` and a remainder;
    with shared prefixes the trie can leave any remainder."""
    lo, hi = int(mix["prompt_len"].get("min", 1)), int(
        mix["prompt_len"]["max"])
    if mix["prompt_len"]["dist"] == "fixed":
        lo = hi = int(mix["prompt_len"]["value"])
    pc = engine.get("prefill_chunk")
    window = int(engine["window"])
    if mix.get("shared_prefix"):
        lo = 1
    chunk_lens = set()
    for length in range(lo, hi + 1):
        if pc is None or length <= pc:
            chunk_lens.add(length)
        else:
            chunk_lens.add(pc)
            chunk_lens.add(length % pc or pc)
    buckets = sorted({_pow2_bucket(c, window) for c in chunk_lens})
    rows = [1 << i for i in range(int(engine["slots"]).bit_length())
            if 1 << i <= engine["slots"]]
    return [(k, pb) for pb in buckets for k in rows]


def warm(eng, vocab: int, mix: dict, engine: dict, seed: int) -> dict:
    """Drive the engine directly (the server's driver thread sleeps while
    no HTTP request is outstanding) through every program the window can
    reach, deterministically, then reset it.  One program after the other,
    on this thread: compiling several at once from a thread pool ended in
    a stack overflow inside the TPU compiler (TpuBroadcastRewriter; my
    chip runs, PR 23), so a cold first run compiles for some ten minutes.

    * one wave of ``slots + 1`` requests, one of them 16 tokens long:
      with work waiting the engine clamps a decode chunk to the next
      retirement, which walks ``n`` through 8, 4, 2, 1 and back to the
      full chunk;
    * for every (rows, bucket): that many prompts of that bucket at once,
      one token each (``max_new_tokens=1`` finishes at admission, so no
      decode chunk is paid for it).  Under chunked prefill a prompt one
      whole chunk longer also takes the whole-chunk program.
    """
    rng = np.random.Generator(np.random.PCG64([int(seed), 99]))
    slots, chunk = int(engine["slots"]), int(engine["chunk"])
    pc = engine.get("prefill_chunk")
    lo = max(2, int(mix["prompt_len"].get("min", 2)))
    facts = {"waves": 0, "requests": 0}

    def wave(lengths, new_tokens):
        for n, m in zip(lengths, new_tokens):
            eng.submit(rng.integers(0, vocab, int(n)), int(m))
        out = eng.run()
        facts["waves"] += 1
        facts["requests"] += len(out)

    wave([lo] * (slots + 1), [chunk] + [3 * chunk] * slots)
    shapes = prefill_shapes(mix, engine)
    for k, pb in shapes:
        length = min(pb, int(mix["prompt_len"]["max"]))
        if pc is not None and pb <= pc and mix["prompt_len"]["max"] > pc:
            # remainder bucket: ride behind one whole chunk where the
            # mix has prompts that long, so (k, pc) is warmed as well
            length = min(pc + pb, int(mix["prompt_len"]["max"]))
            if _pow2_bucket(length - pc, engine["window"]) != pb:
                length = pb
        wave([length] * k, [1] * k)
    # admission writes the WHOLE prompt, padded to its own power-of-two
    # bucket, into the token buffer: one small program per such bucket
    hi = int(mix["prompt_len"]["max"])
    for whole in sorted({_pow2_bucket(n, int(engine["window"]))
                         for n in range(lo, hi + 1)}):
        wave([min(whole, hi)], [1])
    eng.pop_timings()
    eng.reset()
    facts["prefill_shapes"] = len(shapes)
    return facts


# ---------------------------------------------------------------------------
# the rig: one server, warmed once, loaded as often as asked
# ---------------------------------------------------------------------------

class Rig:
    def __init__(self, run):
        import jax

        from benchmark import weights
        from benchmark.entries.train import build_spec

        from autodist_tpu.serving.server import serve

        self.run = run
        cell = run.cell
        self.engine_kwargs = dict(cell.workload["engine"])
        self.vocab = int(cell.config["vocab_size"])
        spec = build_spec(cell.config)
        shapes = jax.eval_shape(spec.init, jax.random.key(0))
        self.params = weights.make_weights(shapes, run.seed)
        with run.spans("bench/serve()"):
            self.srv = serve(spec, self.params, port=0, paged=True,
                             **self.engine_kwargs)
        with run.spans("bench/warm"):
            before = run.watch.snapshot()
            run.counters["warm"] = warm(
                self.srv.engine, self.vocab, cell.traffic,
                self.engine_kwargs, run.seed)
            after = run.watch.snapshot()
            run.counters["warm"]["cache_misses"] = (
                after["misses"] - before["misses"])
            run.counters["warm"]["cache_hits"] = (
                after["hits"] - before["hits"])
        print("set-up:", json.dumps(run.counters["warm"]), flush=True)
        self.scratch = os.path.join(cell.root, ".bench_scratch", "load")
        os.makedirs(self.scratch, exist_ok=True)

    # -- the child ---------------------------------------------------------
    def spawn(self, reqs, seconds: float, drain_s: float, tag: str):
        host, port = self.srv.address
        plan = {"host": host, "port": port, "seconds": seconds,
                "drain_s": drain_s, "requests": reqs}
        stem = os.path.join(self.scratch, f"{self.run.cell.name}.{tag}")
        with open(stem + ".plan.json", "w") as f:
            json.dump(plan, f)
        child = subprocess.Popen(
            [sys.executable, os.path.join(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))), "loadgen.py"),
             "--plan", stem + ".plan.json", "--out", stem + ".out.json"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        if child.stdout.readline().strip() != "ready":
            child.kill()
            child.wait()
            raise SystemExit("benchmark: the load generator did not start")
        return child, stem + ".out.json"

    def load(self, child, out_path, seconds: float, drain_s: float,
             on_tick=None):
        """Say go, wait for the child, return (t0, records)."""
        t0 = time.monotonic() + 0.05
        child.stdin.write(f"go {t0!r}\n")
        child.stdin.flush()
        try:
            while child.poll() is None:
                if on_tick is not None:
                    on_tick(time.monotonic() - t0)
                time.sleep(0.05)
                if time.monotonic() - t0 > seconds + drain_s + 60:
                    raise SystemExit("benchmark: the load generator hung")
        finally:
            if child.poll() is None:
                child.kill()
            child.wait()
        if child.returncode != 0:
            raise SystemExit(f"benchmark: the load generator exited "
                             f"{child.returncode}")
        with open(out_path) as f:
            return t0, json.load(f)

    def wait_idle(self, timeout_s: float = 60.0) -> bool:
        t_end = time.monotonic() + timeout_s
        while not self.srv.idle():
            if time.monotonic() > t_end:
                return False
            time.sleep(0.02)
        return True

    def close(self):
        srv, self.srv = self.srv, None
        if srv is not None:
            srv.close()


def histogram_counts(metrics_text: str, name: str) -> dict:
    """``{upper bound: cumulative count}`` of one Prometheus histogram in
    a ``/metrics`` body."""
    out = {}
    for line in metrics_text.splitlines():
        if line.startswith(name + "_bucket"):
            le = line.split('le="', 1)[1].split('"', 1)[0]
            out[float("inf") if le == "+Inf" else float(le)] = float(
                line.rsplit(" ", 1)[1])
    return out


def summarize(recs: list, seconds: float) -> dict:
    """Client-side numbers of one window (times in seconds from t0)."""
    ok = [r for r in recs if r.get("status") == "ok"]
    failed = [r for r in recs if r.get("status") not in ("ok", "unfinished")]
    unfinished = [r for r in recs if r.get("status") == "unfinished"]
    in_window = [r for r in ok if r["done_s"] <= seconds]
    ttft = [(r["first_s"] - r["due_s"]) * 1e3 for r in ok]
    tpot = [(r["done_s"] - r["first_s"]) / (r["asked"] - 1) * 1e3
            for r in ok if r["asked"] > 1]
    late = [(r["sent_s"] - r["due_s"]) * 1e3 for r in recs if "sent_s" in r]

    def backlog(t):
        return sum(1 for r in recs if r.get("sent_s", 1e18) <= t
                   and r.get("done_s", 1e18) > t)

    return {"sent": len(recs), "ok": len(ok), "failed": len(failed),
            "unfinished": len(unfinished),
            "failed_kinds": sorted({r.get("status") for r in failed}),
            "out_tokens_in_window": sum(r["asked"] for r in in_window),
            "completed_in_window": len(in_window),
            "ttft_ms": ttft, "tpot_ms": tpot, "late_ms": late,
            "backlog_mid": backlog(seconds / 2),
            "backlog_end": backlog(seconds)}


# ---------------------------------------------------------------------------
# correctness: a sample of what the window finished, against the reference
# ---------------------------------------------------------------------------

def sample_finished(recs: list, reqs: list, seed: int, n: int) -> list:
    """``n`` of the requests the window finished, drawn from the seed,
    the longest (prompt + served) among them."""
    ok = [r for r in recs if r.get("status") == "ok"]
    if not ok:
        return []
    longest = max(ok, key=lambda r: (r["prompt_len"] + r["asked"], -r["i"]))
    rest = [r for r in ok if r["i"] != longest["i"]]
    rng = np.random.Generator(np.random.PCG64([int(seed), 11]))
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    chosen = [longest] + [rest[int(j)] for j in sorted(pick)]
    return [(reqs[r["i"]]["prompt"], r["new_tokens"]) for r in chosen]


def served_gaps(params, samples: list, window: int, control=None) -> dict:
    """Reference pass over each sampled prompt with its served tokens.
    Returns the widest and mean gap by which a served token's logit lies
    below the reference's best, and the share of served tokens that are
    not the reference's first choice.  With ``control`` (a dtype) the
    tokens judged are not the served ones but the ones the reference
    computed in that precision puts first at the same positions."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import gpt2

    ref = gpt2.to_reference(params)
    gaps = []
    for prompt, served in samples:
        seq = np.zeros(window, np.int32)
        total = len(prompt) + len(served)
        seq[:len(prompt)] = prompt
        seq[len(prompt):total] = served
        tokens = jnp.asarray(seq)
        if control is None:
            chosen = jnp.asarray(np.roll(seq, -1))   # row t judges t + 1
        else:
            chosen = gpt2.first_choices(ref, tokens, control)
        with jax.default_matmul_precision("highest"):
            g = np.asarray(gpt2.gaps_below_best(ref, tokens, chosen))
        gaps.append(g[len(prompt) - 1:total - 1])
    g = np.concatenate(gaps)
    return {"tokens": int(g.size), "gap_max": float(g.max()),
            "gap_mean": float(g.mean()),
            "not_first_share": float((g > 0).mean())}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(run):
    from benchmark import traffic

    cell = run.cell
    drain_s = float(cell.workload.get("drain_s", 30.0))
    reqs = traffic.requests(cell.traffic, int(cell.config["vocab_size"]),
                            run.seconds, run.seed)
    print("traffic:", json.dumps(traffic.describe(reqs)), flush=True)

    rig = Rig(run)
    try:
        return _measure(run, rig, reqs, drain_s)
    finally:
        rig.close()       # also on the way out of a failure: the server's
        #                   threads must not outlive the interpreter


def _measure(run, rig, reqs, drain_s):
    cell = run.cell
    limits = cell.workload["limits"]
    eng = rig.srv.engine
    child, out_path = rig.spawn(reqs, run.seconds, drain_s, "window")
    stats0 = rig.srv.stats()
    hist0 = histogram_counts(rig.srv.render_metrics(),
                             "autodist_serving_queue_wait_seconds")

    run.begin_window()
    # begin_window() stamps set-up's end; the child starts 50 ms later
    t0, recs = rig.load(child, out_path, run.seconds, drain_s,
                        on_tick=run.tracer.poll)
    run.tracer.stop()
    run.end_window()

    drained = rig.wait_idle()
    stats1 = rig.srv.stats()
    hist1 = histogram_counts(rig.srv.render_metrics(),
                             "autodist_serving_queue_wait_seconds")
    leak = None
    if drained:
        try:
            eng.assert_no_leaks()
            leak = 0
        except AssertionError as e:
            print("leak:", e, flush=True)
            leak = 1
    rig.close()

    s = summarize(recs, run.seconds)
    run.attempted = s["sent"]
    run.failed = s["failed"]
    run.e2e["serve_out_tokens_s"] = s["out_tokens_in_window"] / run.seconds
    run.e2e["serve_ttft_ms_p95"] = percentile(s["ttft_ms"], 95)
    run.e2e["serve_tpot_ms_p95"] = percentile(s["tpot_ms"], 95)
    run.counters.update(
        client={k: v for k, v in s.items()}, stats0=stats0, stats1=stats1,
        queue_wait_hist=(hist0, hist1), engine=rig.engine_kwargs,
        seconds=run.seconds,
        mean_live_tokens=_mean_live_tokens(recs, run.seconds))

    # the server's state goes before the reference comes
    run.counters["memory_peak_bytes"] = memory_peak_bytes(run.devices)
    params, rig.params = rig.params, None
    del eng
    gc.collect()

    run.check("requests_failed", s["failed"], 0)
    if cell.workload.get("expect_all_finished", False):
        run.check("requests_unfinished", s["unfinished"], 0)
    run.check("engine_drained_and_no_leaks",
              1 if (drained and leak == 0) else 0, 1, at_most=False)
    samples = sample_finished(recs, reqs, run.seed,
                              int(cell.workload.get("check_requests", 4)))
    with run.outside_setup(), run.spans("bench/reference"):
        t_ref = time.perf_counter()
        got = served_gaps(params, samples,
                          int(cell.workload["engine"]["window"]))
        run.counters["reference_s"] = time.perf_counter() - t_ref
    run.counters["served_check"] = got
    print("served check:", json.dumps(got), f"reference "
          f"{run.counters['reference_s']:.1f} s (not set-up)", flush=True)
    run.check("served_tokens_checked", got["tokens"],
              limits["served_tokens_checked_min"], at_most=False)
    run.check("served_gap_max", got["gap_max"], limits["served_gap_max"])
    run.check("served_gap_mean", got["gap_mean"], limits["served_gap_mean"])
    return params


def _mean_live_tokens(recs: list, seconds: float) -> float:
    """Time-average over the window of the tokens (prompt + generated so
    far) of the requests that hold a slot, from the client's clock: a
    request is live from its first token to its last."""
    total = 0.0
    for r in recs:
        if "first_s" not in r or "done_s" not in r:
            continue
        a, b = max(0.0, r["first_s"]), min(seconds, r["done_s"])
        if b <= a:
            continue
        # generated grows linearly from 1 to asked between first and done
        span = max(r["done_s"] - r["first_s"], 1e-9)
        g_a = 1 + (r["asked"] - 1) * (a - r["first_s"]) / span
        g_b = 1 + (r["asked"] - 1) * (b - r["first_s"]) / span
        total += (b - a) * (r["prompt_len"] + (g_a + g_b) / 2)
    return total / seconds
