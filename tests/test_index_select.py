"""``ops/index_select.py: dsa_select`` (interpret mode, sizes a test holds)
against what it takes the place of on a TPU: ``models/gqa_dsa_moe_lm.py:
select_keys`` (``index_scores``, ``top_k_mask``, ``pack_selection``).

Equal BIT FOR BIT means the same picks, so no case may turn on the order of
a float32 sum (the kernel adds six bfloat16 products 384 deep where the plain
form adds 64 float32 ones).  Two kinds of inputs make sure of that: ``grid``
(every number a multiple of 1/8 under 4: every product and sum is exact in
either order, and the scores tie everywhere) and ``spread`` (normal floats
from the first seed on which the ``topk``-th and the next score of every row
lie 1e-4 apart, some fifty roundings of scores of 30; one head's product is
kept positive so that no score is the exact zero of sixteen relus).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.models.gqa_dsa_moe_lm import (
    gqa_dsa_moe_lm,
    index_scores,
    select_keys,
)
from autodist_tpu.ops import index_select
from autodist_tpu.ops.flash_attention import unpack_selection
from autodist_tpu.ops.index_select import (
    dsa_select,
    ordered_ints,
    split_terms,
    stacked_terms,
)
from autodist_tpu.ops.topk_select import ordered_bits
from autodist_tpu.telemetry import registry, step_values

from test_gqa_dsa_moe_lm import GAIN, RTOL, TINY, by_top_k

HEADS, DIM = 3, 8


def plain_scores(qi, ki, w):
    with jax.default_matmul_precision("highest"):
        return np.asarray(index_scores(qi, ki, w))


def operands(t, kind, seed, topk=None):
    while True:
        rng = np.random.RandomState(seed)
        qi, ki, w = rng.randn(t, HEADS, DIM), rng.randn(t, DIM), rng.randn(
            t, HEADS)
        if kind == "grid":
            qi, ki, w = (np.clip(np.round(a * 4) / 8, -2, 2)
                         for a in (qi, ki, w))
        else:
            qi[:, 0, 0] = ki[:, 0] = 6.0    # head 0's product stays positive
        out = tuple(jnp.asarray(a, jnp.float32) for a in (qi, ki, w))
        if kind == "grid" or gap_at_threshold(plain_scores(*out),
                                              topk) > 1e-4:
            return out
        seed += 1000


def tiles_that_tie(score, topk, bq):
    """1 for every tile of ``bq`` rows in which a row past ``topk`` has
    more scores at or over its ``topk``-th largest than ``topk``."""
    t = score.shape[0]
    seen = np.tril(np.ones((t, t), bool))
    ranked = np.sort(np.where(seen, score + 0.0, -np.inf), axis=-1)[:, ::-1]
    crowded = np.zeros(t, bool)
    for row in range(topk, t):
        crowded[row] = (score[row, :row + 1] >= ranked[row, topk - 1]
                        ).sum() > topk
    return crowded.reshape(t // bq, bq).any(axis=1).astype(np.int32)


def gap_at_threshold(score, topk):
    """The least distance, over the rows past ``topk``, between a row's
    ``topk``-th score and its next."""
    t = score.shape[0]
    seen = np.tril(np.ones((t, t), bool))
    ranked = np.sort(np.where(seen, score, -np.inf), axis=-1)[:, ::-1]
    return (ranked[topk:, topk - 1] - ranked[topk:, topk]).min()


# (T, topk, bq, bk): T no multiple of topk; a tile that straddles row topk
# (64 rows a tile, topk 40 and 100); tiles of rows that take every earlier
# key (the first of 32-row tiles under topk 64, and of 64 under 100); one
# tile for the whole sequence; a tile wider than a key block and narrower
_SHAPES = [(256, 40, 64, 32), (192, 64, 32, 64), (128, 40, 128, 32),
           (256, 100, 64, 64), (160, 32, 32, 32), (384, 130, 128, 64)]


@pytest.mark.parametrize("kind", ["grid", "spread"])
@pytest.mark.parametrize("t,topk,bq,bk", _SHAPES)
def test_words_are_select_keys_words_bit_for_bit(t, topk, bq, bk, kind):
    qi, ki, w = operands(t, kind, t + topk, topk)
    want = jax.jit(functools.partial(select_keys, topk=topk, rows=bk,
                                     block_k=bk))(qi, ki, w)
    words, ties = dsa_select(qi, ki, w, topk=topk, block_k=bk, block_q=bq,
                             interpret=True)
    assert words.shape == (t // 32, t) and words.dtype == jnp.int32
    assert (np.asarray(words) == np.asarray(want)).all()
    picked = np.asarray(unpack_selection(words, block_k=bk))
    assert (picked.sum(-1) == np.minimum(np.arange(t) + 1, topk)).all()
    assert (picked[:topk] == np.tril(np.ones((t, t), bool))[:topk]).all()
    score = plain_scores(qi, ki, w)
    if kind == "spread":
        assert not np.asarray(ties).any()
    else:
        assert (np.asarray(ties) == tiles_that_tie(score, topk, bq)).all()


@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("topk", [1, 17, 64, 150, 200])
def test_selection_is_lax_top_k_and_the_flag_rises_only_with_ties(topk,
                                                                  ties):
    """The ``ties`` cases of ``test_top_k_mask_is_lax_top_k``: scores
    quantised to a few dozen values tie at every threshold, and the set is
    the one ``jax.lax.top_k`` names (a tie goes to the lower position)."""
    t, bq, bk = 256, 64, 32
    qi, ki, w = operands(t, "grid" if ties else "spread", topk, topk)
    words, flags = dsa_select(qi, ki, w, topk=topk, block_k=bk, block_q=bq,
                              interpret=True)
    score = plain_scores(qi, ki, w)
    causal = np.tril(np.ones((t, t), bool))
    got = np.asarray(unpack_selection(words, block_k=bk))
    assert (got == by_top_k(score + 0.0, topk, causal)).all()
    expected = tiles_that_tie(score, topk, bq)
    assert (np.asarray(flags) == expected).all()
    assert expected.any() == ties


@pytest.mark.parametrize("seed,scale", [(0, 1.0), (1, 30.0), (2, 1e-3)])
def test_stacked_three_passes_are_highest_to_float32_rounding(seed, scale):
    """``k3 . q3[j]`` (six exact bfloat16 products two to a pass, summed in
    float32) against ``index_scores``: within the rounding of the 64-term
    sums and of the 16-term sum over the heads."""
    rng = np.random.RandomState(seed)
    qi = jnp.asarray(rng.randn(64, 16, 64) * scale, jnp.float32)
    ki = jnp.asarray(rng.randn(96, 64) * scale, jnp.float32)
    w = jnp.asarray(rng.randn(64, 16), jnp.float32)
    twice = lambda x: jnp.concatenate([x, x], axis=-1)      # noqa: E731
    k3 = stacked_terms(twice(ki), keys=True)
    q3 = [stacked_terms(twice(qi[:, j]), keys=False) for j in range(16)]
    assert q3[0].shape == (64, 384) and k3.shape == (96, 384)
    assert q3[0].dtype == k3.dtype == jnp.bfloat16
    got = np.zeros((64, 96), np.float32)
    for j in range(16):
        s = jnp.einsum("qc,kc->qk", q3[j], k3,
                       preferred_element_type=jnp.float32)
        got = got + np.asarray(w[:, j, None] * jax.nn.relu(s))
    want = plain_scores(qi, ki, w)
    # what the terms could add up to, had every product one sign
    size = np.einsum("qj,qjc,kc->qk", np.abs(np.asarray(w)),
                     np.abs(np.asarray(qi)), np.abs(np.asarray(ki)))
    assert (np.abs(got - want) <= 2.0 ** -21 * size).all()
    assert np.abs(got - want).max() > 0 or scale == 1.0


@pytest.mark.parametrize("scale", [1.0, 1e-20, 1e20])
def test_three_terms_are_the_number_exactly(scale):
    x = np.random.RandomState(3).randn(4096).astype(np.float32) * np.float32(
        scale)
    x[:4] = [0.0, -0.0, 1.0, -1.0]
    terms = split_terms(jnp.asarray(x))
    hi, mid, lo = (np.asarray(term) for term in terms)
    assert ((hi + mid) + lo == x).all()
    # and each is a bfloat16 number: the kernel's conversion rounds nothing
    assert all((term.astype(jnp.bfloat16).astype(jnp.float32) == term).all()
               for term in terms)


def test_ordered_ints_are_ordered_bits_of_the_number_plus_zero():
    x = jnp.asarray([-np.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, np.inf],
                    jnp.float32)
    got = np.asarray(ordered_ints(x)).astype(np.int64)
    want = np.asarray(ordered_bits(x + 0.0)).astype(np.int64) - (1 << 31)
    assert (got == want).all()
    assert got[3] == got[4] and (np.diff(np.delete(got, 3)) > 0).all()


def test_blocks_and_lengths_that_do_not_fit_are_refused():
    qi, ki, w = operands(96, "grid", 0)
    with pytest.raises(ValueError, match="blocks of 64"):
        dsa_select(qi, ki, w, topk=16, block_k=64, interpret=True)
    with pytest.raises(ValueError, match="blocks of 48"):
        dsa_select(qi, ki, w, topk=16, block_k=48, interpret=True)


# ---------------------------------------------------------------------------
# in the model
# ---------------------------------------------------------------------------

def gauges(name):
    return {tuple(sorted(m.labels.items())): m.value
            for m in registry.DEFAULT_REGISTRY.metrics() if m.name == name}


@pytest.mark.parametrize("path", ["plain", "kernel"])
def test_model_selects_by_the_kernel_where_kernels_are_compiled(monkeypatch,
                                                                path):
    """Off a TPU the model takes ``select_keys``; where Pallas kernels are
    compiled (here: the answer patched, the kernel interpreted) the same
    loss comes from ``dsa_select``, the rows are counted under its path
    and the tiles' flags leave as a step value."""
    spec = gqa_dsa_moe_lm(**TINY)
    params = jax.tree.map(lambda a: a * GAIN, spec.init(jax.random.key(0)))
    batch = spec.make_batch(np.random.RandomState(0), 2)
    plain = float(jax.jit(spec.loss_fn)(params, batch))
    if path == "kernel":
        monkeypatch.setattr(index_select, "_use_interpret", lambda: False)
        monkeypatch.setattr(index_select, "dsa_select", functools.partial(
            dsa_select, interpret=True))
        spec = gqa_dsa_moe_lm(**TINY)    # a checkpoint keeps what it traced
    collector = step_values.Collector.wanted_by(spec.loss_fn)
    loss, (_, values) = jax.jit(collector.wrap(spec.loss_fn, False))(
        params, batch)
    assert abs(float(loss) - plain) <= RTOL * plain
    t, topk, layers = TINY["seq_len"], TINY["topk"], TINY["num_layers"]
    # the plain form's blocks of 32 rows end at row topk; the kernel's one
    # tile of 96 straddles it and forms every row's scores
    scored = 2 * layers * (t - topk if path == "plain" else t)
    rows = gauges("autodist_dsa_select_rows_per_step")
    assert rows == {(("path", path),): scored,
                    (("path", "kernel" if path == "plain" else "plain"),): 0,
                    (("path", "whole"),): 2 * layers * t - scored}
    assert ("dsa_tie_searches" in values) == (path == "kernel")
    if path == "kernel":
        assert values["dsa_tie_searches"].shape == ()
        collector.publish(jax.device_get(values))
        assert gauges("autodist_dsa_tie_searches_per_step") == {
            (): int(values["dsa_tie_searches"])}
        # and the gradient: the indexer's leaves get zeros, as ever
        grads = jax.grad(spec.loss_fn)(params, batch)
        assert all(not np.asarray(g).any() for g in jax.tree.leaves(
            grads["layers_0"]["indexer"]))
