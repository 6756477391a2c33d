"""``models/gqa_dsa_moe_lm.py``, ``ops/topk_select.py`` and the softmax
scoring of ``parallel/moe.py: routed_moe_ffn`` against the plain reference
``benchmark/reference/keye_vl2.py`` (CPU, tiny widths, seeded weights).

Tolerances as in ``test_mla_moe_lm.py``: a CPU multiplies float32
exactly, so program and reference differ by the order of their float32
sums alone, a few 1e-6 after two layers and a backward pass; ``RTOL`` is
2e-5.  The weights are the benchmark's normal(0, 0.02) times ``GAIN``, so
that the indexer's scores spread and a changed selection moves the loss by
1e-3 or more; no seed here has two index scores within 1e-6 of a tie at
the ``topk``-th place.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.models.gqa_dsa_moe_lm import (
    KEPT_NAMES,
    SELECTION_NAME,
    gqa_dsa_moe_lm,
    index_scores,
    select_keys,
)
from autodist_tpu.models.transformer import dense_selected_attention
from autodist_tpu.ops import flash_attention
from autodist_tpu.ops.flash_attention import (
    pack_selection,
    pairs_computed,
    unpack_selection,
)
from autodist_tpu.ops.topk_select import ordered_bits, top_k_mask
from autodist_tpu.parallel.moe import (
    init_routed_moe_params,
    routed_moe_ffn,
    chunk_rows,
)
from benchmark.reference import keye_vl2 as ref

import _routed_cases as routed_cases

RTOL = 2e-5
GAIN = 8.0
TOPK = 32
TINY = dict(vocab_size=61, num_layers=2, d_model=32, num_heads=4,
            num_kv_heads=2, head_dim=16, index_heads=2, index_dim=8,
            topk=TOPK, d_expert=12, num_experts=16, top_k=3, seq_len=96,
            block_k=32, index_rows=32, moe_slice=96)
FLASH = functools.partial(flash_attention, interpret=True, block_q=32,
                          block_k=32)


def settings(first_held=0, top_k=3, topk=TOPK, train_router=True):
    return ref.Settings(top_k=top_k, first_held=first_held, topk=topk,
                        theta=1e7, eps=1e-6, train_router=train_router)


seeded = functools.partial(routed_cases.seeded, gain=GAIN)
tokens, flat, rel = routed_cases.tokens, routed_cases.flat, routed_cases.rel


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("held,remat,chunk,train_router,attn", [
    (None, "none", None, True, None),
    ((4, 4), "full", 32, False, None),
    ((4, 4), "full", None, True, FLASH),
    ((0, 2), "none", 32, True, FLASH),
])
def test_loss_and_every_gradient_match_the_reference(held, remat, chunk,
                                                     train_router, attn):
    spec = gqa_dsa_moe_lm(**TINY, experts_held=held, remat=remat,
                          xent_chunk=chunk, train_router=train_router,
                          attn_fn=attn)
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 3)
    batch = tokens(5)
    s = settings(held[0] if held else 0, train_router=train_router)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(spec.loss_fn))(
            params, {"tokens": batch})
        want, want_grads = ref.loss_and_grads(params, jnp.asarray(batch),
                                              row_block=2, s=s)
    assert abs(float(loss) - float(want)) < RTOL
    routed_cases.assert_every_gradient_matches(
        grads, want_grads, RTOL, lambda name: "/indexer/" in name or (
            not train_router and "router" in name))


@pytest.mark.parametrize("topk", [16, 64, 96])
def test_another_selection_is_another_loss(topk):
    """The comparison sees the mechanism: the same weights under a
    selection of half as many, twice as many or all earlier keys give a
    loss that differs by 1e-3 or more."""
    batch = {"tokens": tokens(5)}
    spec = gqa_dsa_moe_lm(**TINY)
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 3)
    other = gqa_dsa_moe_lm(**dict(TINY, topk=topk))
    with jax.default_matmul_precision("highest"):
        gap = abs(float(jax.jit(spec.loss_fn)(params, batch))
                  - float(jax.jit(other.loss_fn)(params, batch)))
        want = float(ref.loss_and_grads(
            params, jnp.asarray(batch["tokens"]), row_block=2,
            s=settings(topk=topk))[0])
        assert abs(float(jax.jit(other.loss_fn)(params, batch)) - want) \
            < RTOL
    assert gap > 1e-3


@pytest.mark.parametrize("router", ["even", "collapsed"])
def test_three_session_steps_match_the_reference_adamw(router):
    """Through ``AutoDist.capture(has_aux=True) -> create_distributed_
    session -> run``: three steps' losses and the parameters after them
    against the reference under AdamW written out (the indexer's leaves
    move by the decoupled decay alone); the gauges are set at trace
    time, but the ``computed`` rows of the expert layers: those are the
    chunks the LAST STEP's calls took (2 layers, each ONE call over 2
    slices of 96 tokens x 3 picks, a quarter of them expected here).  An
    even router leaves every call inside its first chunk of 288 places
    (twice the even load, and a slice's picks); a router that has
    collapsed (all weights zero, not trained: every token picks experts 0,
    1 and 2, which are held) makes every call take both chunks."""
    from autodist_tpu import strategy as strategies
    from autodist_tpu.autodist import (AutoDist,
                                       _reset_default_autodist_for_testing)
    from autodist_tpu.mesh import build_mesh
    from autodist_tpu.telemetry.registry import DEFAULT_REGISTRY

    collapsed = router == "collapsed"
    first = 0 if collapsed else 4
    spec = gqa_dsa_moe_lm(**TINY, experts_held=(first, 4),
                          return_counts=True, attn_fn=FLASH,
                          train_router=not collapsed)
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 11)
    if collapsed:
        for i in range(TINY["num_layers"]):
            moe = params[f"layers_{i}"]["moe"]
            moe["router"] = jnp.zeros_like(moe["router"])
    batches = [jnp.asarray(tokens(20 + i)) for i in range(3)]
    assert chunk_rows(2 * 96 * 3, 4, 16, 96 * 3) == 288
    rungs = (288, 576)
    with jax.default_matmul_precision("highest"):
        want_losses, _, want_delta, _ = ref.train_steps(
            params, batches, row_block=2,
            s=settings(first, train_router=not collapsed))
        _reset_default_autodist_for_testing()
        ad = AutoDist(strategy_builder=strategies.AllReduce(),
                      mesh_axes={"data": 1})
        with ad.scope():
            ad.capture(params=params, optimizer=optax.adamw(1e-3),
                       loss_fn=spec.loss_fn, sparse_vars=spec.sparse_vars,
                       expert_vars=spec.expert_vars, has_aux=True)
        sess = ad.create_distributed_session(
            mesh=build_mesh({"data": 1}, devices=jax.devices()[:1]))
        _, before = routed_cases.budget_gauges()
        outs = [sess.run({"tokens": np.asarray(b)}) for b in batches[:2]]
        _, two_steps = routed_cases.budget_gauges()
        outs.append(sess.run({"tokens": np.asarray(batches[2])}))
        rows, three_steps = routed_cases.budget_gauges()
        delta = ref.flatten(ref.leaf_diff_norms(
            sess.export_state()[0], params))
    _reset_default_autodist_for_testing()
    for out, want in zip(outs, want_losses):
        assert abs(float(out["loss"]) - want) < RTOL
        assert np.asarray(out["aux"]["tokens_per_expert"]).shape == (2, 4)
    # 2 layers a step, one call each, all taking as many chunks
    taken = rungs[collapsed]
    assert {r: three_steps[r] - two_steps.get(r, 0) for r in rungs} \
        == {r: 2 * (r == taken) for r in rungs}
    assert {r: three_steps[r] - before.get(r, 0) for r in rungs} \
        == {r: 6 * (r == taken) for r in rungs}
    assert rows == 2 * taken
    for name, want in want_delta.items():
        assert abs(delta[name] - want) <= 1e-4 * max(want, 1e-6), name
    gauges = {(m.name, m.labels.get("kind") or m.labels.get("name")): m.value
              for m in DEFAULT_REGISTRY.metrics()
              if m.name.startswith(("autodist_dsa_", "autodist_moe_rows",
                                    "autodist_remat_"))}
    selected = sum(min(t + 1, TOPK) for t in range(96))
    assert gauges[("autodist_dsa_pairs_per_step", "selected")] \
        == 2 * 2 * selected
    assert gauges[("autodist_dsa_pairs_per_step", "computed")] \
        == 2 * 2 * pairs_computed(96, block_k=32) == 2 * 2 * 96 * 96
    # q blocks of 32 against 1, 2 and 3 key blocks of 32
    assert pairs_computed(96, block_q=32, block_k=32) == 6 * 32 * 32
    assert pairs_computed(16384) == 528 * 512 * 512
    assert gauges[("autodist_moe_rows_per_step", "computed")] == rows
    assert gauges[("autodist_moe_rows_per_step", "expected")] \
        == 2 * 2 * 96 * 3 / 4
    # the selection's words: [1, 96 / 32, 96] int32 a sequence and layer
    assert gauges[("autodist_remat_kept_bytes_per_step", SELECTION_NAME)] \
        == 2 * 2 * 3 * 96 * 4
    assert gauges[("autodist_remat_kept_bytes_per_step",
                   "flash_attention/o")] == 2 * 2 * 4 * 96 * 16 * 4


def test_backward_neither_scores_nor_selects_again():
    """With the names kept, the backward of a layer holds one attention
    kernel of its own and no second forward, no index score and no
    selection: what ``KEPT_NAMES`` is for."""
    def count(remat):
        spec = gqa_dsa_moe_lm(**TINY, remat=remat, attn_fn=FLASH)
        params = jax.eval_shape(spec.init, jax.random.key(0))
        jaxpr = jax.make_jaxpr(jax.grad(spec.loss_fn))(
            params, {"tokens": tokens(1)})
        found = routed_cases.primitives(jaxpr.jaxpr)
        return found["pallas_call"], found["top_k"]

    # two layers: a forward and a backward kernel each; the routers' top-k
    assert count("none") == count("full") == (4, 2)
    assert SELECTION_NAME in KEPT_NAMES


# ---------------------------------------------------------------------------
# the selection
# ---------------------------------------------------------------------------

def by_top_k(x, k, valid=None):
    x = np.asarray(x, np.float32)
    if valid is not None:
        x = np.where(valid, x, -np.inf)
    _, places = jax.lax.top_k(jnp.asarray(x), min(k, x.shape[-1]))
    mask = np.zeros(x.shape, bool)
    np.put_along_axis(mask, np.asarray(places), True, -1)
    return mask if valid is None else mask & valid


@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("k", [1, 17, 150, 299, 300, 400])
def test_top_k_mask_is_lax_top_k(k, ties):
    x = np.random.RandomState(k).randn(7, 300).astype(np.float32)
    if ties:
        x = np.round(x * 2) / 2        # a dozen values, ties everywhere
    got = jax.jit(lambda a: top_k_mask(a, k))(jnp.asarray(x))
    assert (np.asarray(got) == by_top_k(x, k)).all()
    assert (np.asarray(got).sum(-1) == min(k, 300)).all()


@pytest.mark.parametrize("k", [50, 103, 104, 200])
def test_top_k_mask_over_causal_rows_shorter_and_longer_than_k(k):
    """Rows 100..106 of a causal triangle see 101..107 keys: under, at
    and over ``k``; ties at the threshold go to the lower position."""
    x = np.round(np.random.RandomState(k).randn(7, 300) * 2) / 2
    valid = np.tril(np.ones((300, 300), bool))[100:107]
    got = np.asarray(jax.jit(lambda a, v: top_k_mask(a, k, v))(
        jnp.asarray(x, jnp.float32), jnp.asarray(valid)))
    assert (got == by_top_k(x, k, valid)).all()
    assert (got.sum(-1) == np.minimum(valid.sum(-1), k)).all()


def test_ordered_bits_keep_the_order_of_floats():
    x = np.array([-np.inf, -3.5, -1e-30, 0.0, 1e-30, 2.0, np.inf],
                 np.float32)
    bits = np.asarray(ordered_bits(jnp.asarray(x))).astype(np.uint64)
    assert (np.diff(bits.astype(np.int64)) > 0).all()


@pytest.mark.parametrize("t,block", [(96, 32), (128, 64), (64, 64)])
def test_selection_words_round_trip(t, block):
    picked = jnp.asarray(np.random.RandomState(t).rand(2, t, t) < 0.3)
    words = pack_selection(picked, block_k=block)
    assert words.shape == (2, t // 32, t) and words.dtype == jnp.int32
    assert (np.asarray(unpack_selection(words, block_k=block))
            == np.asarray(picked)).all()


def test_select_keys_is_the_references_selection():
    """``select_keys`` (blocks of rows against the keys before them, a
    threshold, packed words) against the reference's mask from
    ``lax.top_k`` over whole rows; rows before ``topk`` select all."""
    rng = np.random.RandomState(2)
    t, topk = 128, 40
    qi = jnp.asarray(rng.randn(t, 2, 8), jnp.float32)
    ki = jnp.asarray(rng.randn(t, 8), jnp.float32)
    w = jnp.asarray(rng.randn(t, 2), jnp.float32)
    words = jax.jit(functools.partial(select_keys, topk=topk, rows=32,
                                      block_k=32))(qi, ki, w)
    got = np.asarray(unpack_selection(words, block_k=32))
    with jax.default_matmul_precision("highest"):
        score = np.asarray(index_scores(qi, ki, w))
    causal = np.tril(np.ones((t, t), bool))
    assert (got == by_top_k(score, topk, causal)).all()
    assert (got[:topk] == causal[:topk]).all()
    assert (got.sum(-1) == np.minimum(np.arange(t) + 1, topk)).all()


# ---------------------------------------------------------------------------
# the experts: softmax routing and the share
# ---------------------------------------------------------------------------

def moe_layer(seed, held=None):
    p = init_routed_moe_params(jax.random.key(seed), 32, 12, 16,
                               experts_held=held, selection_bias=False)
    assert "router_bias" not in p and "shared" not in p
    return dict(p, router=p["router"] * 40.0)


def share_of(params, first, count):
    return dict(params, experts=jax.tree.map(
        lambda a: a[first:first + count], params["experts"]))


def test_softmax_top_k_routing_matches_the_reference():
    params = moe_layer(1)
    x = jax.random.normal(jax.random.key(2), (2, 24, 32))
    y, counts = routed_moe_ffn(params, x, top_k=4, scoring="softmax")
    assert rel(y, ref.experts(x, params, s=settings(top_k=4))) < RTOL
    g = ref.expert_weights(x, params, settings(top_k=4))
    assert np.allclose(np.asarray(g.sum(-1)), 1.0, atol=1e-6)
    assert ((np.asarray(g) > 0).sum(-1) == 4).all()
    assert int(counts.sum()) == 2 * 24 * 4
    # the sigmoid router of the same weights picks other weights
    y2, _ = routed_moe_ffn(dict(params, router_bias=jnp.zeros(16)), x,
                           top_k=4)
    assert rel(y2, y) > 1e-3
    with pytest.raises(ValueError, match="scoring"):
        routed_moe_ffn(params, x, top_k=4, scoring="tanh")


def test_eight_shares_add_up_to_the_whole_layer():
    """Eight chips hold 2 of 16 experts each: what each computes for its
    own experts adds up to the uncut reference's layer output, and each
    share is the reference's share."""
    params = moe_layer(3)
    x = jax.random.normal(jax.random.key(4), (2, 24, 32))
    whole = ref.experts(x, params, s=settings(top_k=4))
    parts, counts = [], []
    for chip in range(8):
        y, n = routed_moe_ffn(share_of(params, 2 * chip, 2), x, top_k=4,
                              experts_held=(2 * chip, 2), scoring="softmax")
        parts.append(y)
        counts.append(n)
        want = ref.experts(x, share_of(params, 2 * chip, 2),
                           s=settings(2 * chip, top_k=4))
        assert rel(y, want) < RTOL
    assert rel(sum(parts), whole) < RTOL
    assert int(jnp.concatenate(counts).sum()) == 2 * 24 * 4   # every pick


# chunks of the sorted order (PR 39) under the softmax router; the sigmoid
# router's cases and the chunk's own are in test_mla_moe_lm.py
@pytest.mark.parametrize("load", sorted(routed_cases.LOADS))
def test_chunks_equal_one_wide_chunk_to_the_bit(load):
    routed_cases.assert_chunks_equal_one_wide_chunk("softmax", load)


@pytest.mark.parametrize("load", [16, 40, 100])
def test_compiled_chunks_match_the_reference(load):
    """Jitted, over one, two and four chunks, the layer and the gradient
    through it match the plain reference."""
    params = routed_cases.layer("softmax")
    x = routed_cases.tokens_routing(load)
    s = settings(routed_cases.HELD[0], top_k=routed_cases.TOP_K)
    value, counts, grads = jax.jit(
        lambda p, x: routed_cases.value_and_gradients(p, x, "softmax"))(
        params, x)
    assert int(counts.sum()) == load
    # routed_cases scales the routed part; the softmax router has no scale
    want, want_grads = jax.value_and_grad(
        lambda p, x: jnp.sum((2.448 * ref.experts(x, p, s=s)) ** 2),
        argnums=(0, 1))(params, x)
    assert rel(value, want) < RTOL
    for name, leaf in flat(want_grads[0]).items():
        assert rel(flat(grads[0])[name], leaf) < RTOL, name
    assert rel(grads[1], want_grads[1]) < RTOL


def test_gradient_holds_one_loop_a_direction_and_fills_no_rows():
    routed_cases.assert_gradient_loops_once_a_direction_and_fills_no_rows(
        "softmax")


@pytest.mark.parametrize("load", [16, 32, 64])
def test_rows_past_the_last_group_are_never_read(load):
    routed_cases.assert_unwritten_rows_are_never_read("softmax", load)


@pytest.mark.parametrize("top_k", [6, 8])
def test_nothing_is_as_wide_as_the_picks(top_k):
    routed_cases.assert_nothing_is_as_wide_as_the_picks("softmax", top_k)


def test_dense_fallback_is_the_kernel():
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 64, 4, 16), jnp.float32)
    k, v = (jnp.asarray(rng.randn(1, 64, 2, 16), jnp.float32)
            for _ in range(2))
    picked = jnp.asarray(np.tril(rng.rand(1, 64, 64) < 0.5)
                         | np.eye(64, dtype=bool))
    words = pack_selection(picked, block_k=32)
    want = dense_selected_attention(q, k, v, True, selection=words,
                                    select_from=1, block_k=32)
    got = FLASH(q, k, v, True, selection=words, select_from=1)
    assert rel(got, want) < RTOL
