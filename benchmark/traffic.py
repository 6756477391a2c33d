"""The one general traffic generator.  A traffic mix is a data file
(``benchmark/traffic/<name>.json``) of parameters that this module reads;
a new mix is a new file, never new code.

Two kinds:

``lm_batches``   training batches: ``seq_len``, ``global_batch``.  Token
                 ids uniform over the vocabulary, a fresh batch per step.

``open_loop``    requests on a schedule, sent whether or not earlier ones
                 have finished.  Parameters::

    rate_per_s        mean arrivals per second
    arrivals          {"process": "poisson", "burst": [lo, hi]}: bursts
                      arrive as a Poisson process, each of lo..hi requests
                      at once; the mean REQUEST rate stays rate_per_s
    prompt_len        {"dist": "lognormal", "median", "sigma", "min", "max"}
    output_len        the same; also {"dist": "uniform", "min", "max"} and
                      {"dist": "fixed", "value"}
    shared_prefix     null, or {"count": k, "len": [lo, hi], "share": s}:
                      a share s of the prompts starts with one of k fixed
                      prefixes (prompt_len counts the prefix in)
    stream            whether each request asks for a token stream
                      (every request is greedy: no temperature is sent)

**Every seed gets the same work.**  The lengths of a window's N requests
are the N mid-quantiles of their distributions, and the gaps between
arrivals the N mid-quantiles of the exponential, scaled to fill the
window: one fixed multiset for a given mix and window.  The seed decides
the ORDER of lengths and gaps (independently) and the token ids.  So two
seeds differ as two shuffles of one deck, not as two decks.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64([int(seed), stream]))


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def lm_batches(mix: dict, vocab: int, seed: int):
    """An endless iterator of int32 [global_batch, seq_len] token arrays."""
    if mix["kind"] != "lm_batches":
        raise ValueError(f"traffic kind {mix['kind']!r} is not lm_batches")
    rng = _rng(seed, 1)
    shape = (int(mix["global_batch"]), int(mix["seq_len"]))
    while True:
        yield rng.integers(0, vocab, shape, dtype=np.int32)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """The n mid-quantiles ((i + 0.5) / n) of a length distribution,
    rounded and clipped: a fixed multiset, no randomness."""
    q = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "fixed":
        vals = np.full(n, float(spec["value"]))
    elif dist == "uniform":
        vals = spec["min"] + q * (spec["max"] - spec["min"])
    elif dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        vals = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo = spec.get("min", 1)
    hi = spec.get("max", math.inf)
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def arrival_times(mix: dict, seconds: float, seed: int, rate: float = None):
    """Due times (seconds from the window's start) of every request of a
    window: bursts at the shuffled mid-quantile gaps of the exponential."""
    rate = float(mix["rate_per_s"] if rate is None else rate)
    arr = mix.get("arrivals", {"process": "poisson", "burst": [1, 1]})
    if arr.get("process", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    lo, hi = arr.get("burst", [1, 1])
    n_req = max(1, int(round(rate * seconds)))
    rng = _rng(seed, 2)
    sizes = []
    while sum(sizes) < n_req:           # burst sizes cycle lo..hi, shuffled
        sizes.extend(range(lo, hi + 1))
    sizes = rng.permutation(sizes)
    cut, total = [], 0
    for s in sizes:
        if total >= n_req:
            break
        cut.append(min(int(s), n_req - total))
        total += cut[-1]
    n_bursts = len(cut)
    q = (np.arange(n_bursts) + 0.5) / n_bursts
    gaps = -np.log1p(-q)
    gaps *= seconds / gaps.sum() * n_bursts / (n_bursts + 0.5)
    starts = np.cumsum(rng.permutation(gaps))
    return np.repeat(starts, cut)


def requests(mix: dict, vocab: int, seconds: float, seed: int,
             rate: float = None) -> list:
    """The window's requests, in due order: dicts with ``due_s``,
    ``prompt`` (token ids), ``max_new_tokens``, ``stream``."""
    if mix["kind"] != "open_loop":
        raise ValueError(f"traffic kind {mix['kind']!r} is not open_loop")
    due = arrival_times(mix, seconds, seed, rate)
    n = len(due)
    p_len = _rng(seed, 3).permutation(quantile_lengths(mix["prompt_len"], n))
    o_len = _rng(seed, 4).permutation(quantile_lengths(mix["output_len"], n))
    tok = _rng(seed, 5)
    shared = mix.get("shared_prefix")
    prefixes, with_prefix = [], np.zeros(n, bool)
    if shared:
        lens = quantile_lengths({"dist": "uniform", "min": shared["len"][0],
                                 "max": shared["len"][1]}, shared["count"])
        prefixes = [tok.integers(0, vocab, int(k)).tolist() for k in lens]
        with_prefix = _rng(seed, 6).permutation(
            np.arange(n) < round(shared.get("share", 1.0) * n))
        which = _rng(seed, 7).integers(0, len(prefixes), n)
    out = []
    for i in range(n):
        body = tok.integers(0, vocab, int(p_len[i])).tolist()
        if with_prefix[i]:
            pre = prefixes[which[i]]
            keep = max(1, len(body) - len(pre))     # at least one own token
            body = (pre + body[:keep])[:max(int(p_len[i]), len(pre) + 1)]
        out.append({"due_s": float(due[i]), "prompt": body,
                    "max_new_tokens": int(o_len[i]),
                    "stream": bool(mix.get("stream", True))})
    return out


def describe(reqs: list) -> dict:
    """Quantiles of what was drawn, for the run's log."""
    def q(vals):
        s = sorted(vals)
        return {p: s[min(len(s) - 1, int(p / 100 * len(s)))]
                for p in (5, 50, 95)} | {"min": s[0], "max": s[-1]}
    return {"requests": len(reqs),
            "prompt_len": q([len(r["prompt"]) for r in reqs]),
            "output_len": q([r["max_new_tokens"] for r in reqs]),
            "last_due_s": reqs[-1]["due_s"]}
