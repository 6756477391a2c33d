"""The traffic generator: the same seed gives the same inputs, every seed
gives the same work in another order, and the lengths have the quantiles
the mix states."""
import json
import os

import numpy as np
import pytest

from benchmark import traffic

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")


def mix(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


BIG_SEED = 2**31 + 12345          # more than 32 signed bits hold


@pytest.mark.parametrize("name", ["chat-steady", "chat-overload"])
def test_requests_are_a_function_of_the_seed(name):
    a = traffic.requests(mix(name), 50257, 30.0, BIG_SEED)
    b = traffic.requests(mix(name), 50257, 30.0, BIG_SEED)
    c = traffic.requests(mix(name), 50257, 30.0, BIG_SEED + 1)
    assert a == b
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]


@pytest.mark.parametrize("name", ["chat-steady", "chat-overload"])
def test_every_seed_gets_the_same_work(name):
    m = mix(name)
    runs = [traffic.requests(m, 50257, 30.0, s) for s in (1, 2, BIG_SEED)]
    n = round(m["rate_per_s"] * 30.0)
    for reqs in runs:
        assert len(reqs) == n
        due = [r["due_s"] for r in reqs]
        assert due == sorted(due) and 0 < due[0] and due[-1] < 30.0
    key = [sorted(len(r["prompt"]) for r in reqs) for reqs in runs]
    out = [sorted(r["max_new_tokens"] for r in reqs) for reqs in runs]
    gaps = [sorted(np.round(np.diff([0.0] + [r["due_s"] for r in reqs]), 9))
            for reqs in runs]
    assert key[0] == key[1] == key[2]
    assert out[0] == out[1] == out[2]
    assert gaps[0] == gaps[1] == gaps[2]
    # ... in another order
    assert [len(r["prompt"]) for r in runs[0]] != \
        [len(r["prompt"]) for r in runs[1]]


def test_length_quantiles_and_limits():
    m = mix("chat-steady")
    reqs = traffic.requests(m, 50257, 60.0, 3)
    p = np.array([len(r["prompt"]) for r in reqs])
    o = np.array([r["max_new_tokens"] for r in reqs])
    assert p.min() >= 32 and p.max() <= 768
    assert o.min() >= 16 and o.max() <= 256
    assert abs(np.median(p) - 256) <= 8
    assert abs(np.median(o) - 96) <= 4
    # log-normal, sigma 0.7: the 84th percentile is median * e^0.7
    assert np.percentile(p, 84) == pytest.approx(256 * np.exp(0.7), rel=0.06)
    assert (p + o).max() <= 1024          # fits the engine's window
    assert all(0 <= t < 50257 for r in reqs[:5] for t in r["prompt"])
    d = traffic.describe(reqs)
    assert d["requests"] == len(reqs) and d["prompt_len"]["max"] == p.max()


def test_overload_is_the_steady_mix_at_a_higher_rate():
    a, b = mix("chat-steady"), mix("chat-overload")
    assert a["prompt_len"] == b["prompt_len"]
    assert a["output_len"] == b["output_len"]
    assert b["rate_per_s"] > a["rate_per_s"]


def test_bursts_keep_the_mean_rate():
    m = dict(mix("chat-steady"),
             arrivals={"process": "poisson", "burst": [8, 16]})
    reqs = traffic.requests(m, 50257, 30.0, 5)
    assert len(reqs) == round(m["rate_per_s"] * 30.0)
    due = [r["due_s"] for r in reqs]
    assert len(set(due)) < len(due) / 6          # arrive in groups


def test_shared_prefixes():
    m = dict(mix("chat-steady"),
             shared_prefix={"count": 3, "len": [64, 128], "share": 1.0})
    reqs = traffic.requests(m, 50257, 20.0, 9)
    heads = {tuple(r["prompt"][:64]) for r in reqs}
    assert len(heads) <= 3


@pytest.mark.parametrize("name,rows", [("pretrain-seq1024-b4", 4),
                                       ("pretrain-seq1024-b16", 16)])
def test_lm_batches(name, rows):
    a = traffic.lm_batches(mix(name), 50257, BIG_SEED)
    b = traffic.lm_batches(mix(name), 50257, BIG_SEED)
    x, y, x2 = next(a), next(a), next(b)
    assert x.shape == (rows, 1024) and x.dtype == np.int32
    assert (x == x2).all() and not (x == y).all()
    assert 0 <= x.min() and x.max() < 50257
    assert len({tuple(r) for r in x}) == rows       # rows all differ
