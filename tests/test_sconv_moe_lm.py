"""``models/sconv_moe_lm.py``, ``routed_decoder``'s dense layers and tied
head and ``routed_moe_ffn``'s ``norm_eps`` against the plain reference
``benchmark/reference/lfm2_moe.py`` (CPU, tiny widths, seeded weights).

A CPU multiplies float32 exactly, so program and reference differ by the
order of their float32 sums alone: ``RTOL`` is ``test_swa_moe_lm.py``'s
2e-5.  The weights are the benchmark's (normal(0, 0.02), leaves named
``scale`` 1) with every matrix times ``GAIN``, so that the routers' scores
spread and a part of the model left out moves the loss.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.models import sconv_moe_lm as model
from autodist_tpu.models.sconv_moe_lm import sconv_moe_lm
from autodist_tpu.ops.flash_attention import flash_attention
from autodist_tpu.parallel.moe import init_routed_moe_params, routed_moe_ffn
from benchmark import flops_sconv_moe as flops
from benchmark.reference import lfm2_moe as ref

import _routed_cases as routed_cases

RTOL = 2e-5
GAIN = 8.0
#: a dense conv layer, an attention and a conv expert layer: every kind
TYPES = ("conv", "full_attention", "conv")
TINY = dict(vocab_size=61, layer_types=TYPES, num_dense_layers=1,
            d_model=32, conv_kernel=3, num_heads=4, num_kv_heads=2,
            head_dim=8, d_ff=48, d_expert=12, num_experts=16, top_k=3,
            seq_len=64, block_k=32, moe_slice=64)
FLASH = functools.partial(flash_attention, interpret=True, block_q=32,
                          block_k=32)


def settings(first_held=0, top_k=3, train_router=True, wrong="",
             layer_types=TYPES, num_dense_layers=1, embed_scale=1.0):
    return ref.Settings(top_k=top_k, first_held=first_held,
                        layer_types=tuple(layer_types),
                        num_dense_layers=num_dense_layers, theta=1e6,
                        eps=1e-5, train_router=train_router,
                        embed_scale=embed_scale, wrong=wrong)


seeded = functools.partial(routed_cases.seeded, gain=GAIN)
tokens = functools.partial(routed_cases.tokens, t=64)
rel = routed_cases.rel


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("held,remat,chunk,train_router,attn,tied,scale", [
    (None, "none", None, True, None, True, 1.0),
    ((4, 4), "full", 32, False, None, False, 1.0),
    ((4, 4), "full", None, True, FLASH, True, 100.0),
    ((0, 2), "none", 32, False, FLASH, False, 1.0),
])
def test_loss_and_every_gradient_match_the_reference(held, remat, chunk,
                                                     train_router, attn,
                                                     tied, scale):
    spec = sconv_moe_lm(**TINY, experts_held=held, remat=remat,
                        xent_chunk=chunk, train_router=train_router,
                        attn_fn=attn, tie_embedding=tied, embed_scale=scale)
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 3)
    assert ("head" in params) is not tied
    assert spec.sparse_vars == (() if tied else ("embed",))
    batch = tokens(5)
    s = settings(held[0] if held else 0, train_router=train_router,
                 embed_scale=scale)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(spec.loss_fn))(
            params, {"tokens": batch})
        want, want_grads = ref.loss_and_grads(params, jnp.asarray(batch),
                                              row_block=2, s=s)
    assert abs(float(loss) - float(want)) < RTOL
    # the selection bias never takes a gradient, the router where the
    # configuration does not train it
    routed_cases.assert_every_gradient_matches(
        grads, want_grads, RTOL, lambda name: name.endswith("router_bias")
        or (not train_router and name.endswith("router")))


@pytest.mark.parametrize("remat", ["none", "full"])
def test_three_adamw_steps_match_the_reference(remat):
    """The benchmark's comparison at a test's size: three steps of
    ``optax.adamw(1e-3)`` on three batches against ``ref.train_steps``:
    every step's loss, the first gradient's norm and the parameters'
    change by leaf."""
    spec = sconv_moe_lm(**TINY, experts_held=(4, 4), remat=remat,
                        xent_chunk=32)
    p0 = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 7)
    batches = [tokens(11 + i) for i in range(3)]
    opt = optax.adamw(1e-3)

    @jax.jit
    def step(params, state, batch):
        loss, grads = jax.value_and_grad(spec.loss_fn)(params, batch)
        updates, state = opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state, loss, grads

    with jax.default_matmul_precision("highest"):
        params, state, losses, first = p0, opt.init(p0), [], None
        for b in batches:
            params, state, loss, grads = step(params, state, {"tokens": b})
            losses.append(float(loss))
            first = ref.flatten(ref.leaf_norms(grads)) if first is None \
                else first
        want = ref.train_steps(ref.to_reference(p0),
                               [jnp.asarray(b) for b in batches],
                               row_block=2, s=settings(4))
    np.testing.assert_allclose(losses, want[0], atol=RTOL)
    delta = ref.flatten(ref.leaf_diff_norms(params, p0))
    assert set(first) == set(want[1]) and set(delta) == set(want[2])
    for got, ref_side in ((first, want[1]), (delta, want[2])):
        floor = float(np.median(list(ref_side.values())))
        for name, value in ref_side.items():
            assert abs(got[name] - value) <= 1e-3 * max(value, floor), name


#: another model in the stated one's place: the reference's ``wrong``
WRONG = ("no_conv", "no_b_gate", "no_qk_norm", "no_rotary")


def stated_weights():
    return seeded(jax.eval_shape(sconv_moe_lm(**TINY).init,
                                 jax.random.key(0)), 3)


@functools.cache
def reference_loss(wrong=""):
    """The reference's loss of the stated model, or of another."""
    with jax.default_matmul_precision("highest"):
        return float(jax.jit(functools.partial(
            ref.sum_loss_fn, s=settings(wrong=wrong)))(
            stated_weights(), jnp.asarray(tokens(5)))) / (2 * 63)


@pytest.mark.parametrize("wrong", WRONG)
def test_another_model_is_another_loss(wrong):
    assert abs(reference_loss(wrong) - reference_loss()) > 1e-4


def test_layers_are_of_two_kinds_by_a_list_and_the_first_are_dense():
    """``layer_types`` says which mixer a layer has and
    ``num_dense_layers`` which FFN: their leaves, and what is called."""
    calls = {"attn": 0}

    def attn(q, k, v, causal, **kw):
        calls["attn"] += 1
        assert q.shape[2:] == (4, 8) and k.shape[2:] == (2, 8) and not kw
        return FLASH(q, k, v, causal)

    types = ("conv", "conv", "full_attention", "conv", "full_attention")
    spec = sconv_moe_lm(**dict(TINY, layer_types=types, num_dense_layers=2),
                        remat="none", attn_fn=attn, return_counts=True)
    params = spec.init(jax.random.key(0))
    assert "head" not in params
    for i, kind in enumerate(types):
        lp = params[f"layers_{i}"]
        assert ("conv" in lp, "attn" in lp) == (kind == "conv",
                                                kind == "full_attention")
        assert ("ffn" in lp, "moe" in lp) == (i < 2, i >= 2)
    conv = params["layers_0"]["conv"]
    assert (conv["w_in"].shape, conv["taps"].shape, conv["w_out"].shape) \
        == ((32, 96), (32, 3), (32, 32))
    assert params["layers_0"]["ffn"]["w_gate"].shape == (32, 48)
    assert params["layers_2"]["moe"]["router_bias"].shape == (16,)
    out = jax.eval_shape(spec.loss_fn, params, {"tokens": tokens(1)})
    # counts from the three expert layers alone
    assert out[1]["tokens_per_expert"].shape == (3, 16)
    assert calls == {"attn": 2}
    assert (spec.config["num_layers"], spec.config["num_dense_layers"]) \
        == (5, 2)
    with pytest.raises(ValueError, match="layer_types"):
        sconv_moe_lm(**dict(TINY, layer_types=("conv", "linear")))
    with pytest.raises(ValueError, match="dense layers"):
        sconv_moe_lm(**dict(TINY, num_dense_layers=3))


def test_total_params_is_inits_count_at_the_published_widths():
    """``flops_sconv_moe.total_params`` from the configuration's keys
    against the program's ``init`` at the cell's own sizes (shapes alone),
    tied and untied."""
    import json
    import os

    from benchmark.entries import train

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           os.pardir, "benchmark", "configs",
                           "lfm2-8b-a1b.ep4-share.json")) as f:
        cfg = json.load(f)
    for tied, count in ((True, 507_820_288), (False, 541_374_720)):
        cfg["tie_embedding"] = cfg["program"]["kwargs"]["tie_embedding"] \
            = tied
        shapes = jax.eval_shape(train.build_spec(cfg).init,
                                jax.random.key(0))
        assert flops.total_params(cfg) == sum(
            x.size for x in jax.tree_util.tree_leaves(shapes)) == count


# ---------------------------------------------------------------------------
# the gated short convolution alone
# ---------------------------------------------------------------------------

def loop_conv(b, c, x, taps):
    """``C * conv(B * X)`` by a direct triple loop (numpy, float64)."""
    t, d = b.shape
    k = taps.shape[1]
    out = np.zeros((t, d))
    for pos in range(t):
        for ch in range(d):
            acc = 0.0
            for i in range(k):
                src = pos - (k - 1) + i
                if src >= 0:
                    acc += taps[ch, i] * b[src, ch] * x[src, ch]
            out[pos, ch] = c[pos, ch] * acc
    return out


def test_gated_conv_against_a_triple_loop_value_and_gradient():
    """At a length (37) and a width (5) that are multiples of nothing."""
    t, d = 37, 5
    bcx = jax.random.normal(jax.random.key(0), (1, t, 3 * d))
    taps = jax.random.normal(jax.random.key(1), (d, 3))
    ct = jax.random.normal(jax.random.key(2), (1, t, d))
    b, c, x = (np.asarray(a[0], np.float64) for a in jnp.split(bcx, 3, -1))
    want = loop_conv(b, c, x, np.asarray(taps, np.float64))
    np.testing.assert_allclose(model.gated_short_conv(bcx, taps)[0], want,
                               atol=1e-5)
    # the reference's form, written apart, agrees
    np.testing.assert_allclose(
        c * np.asarray(ref.short_conv(jnp.asarray(b * x, jnp.float32),
                                      taps)), want, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(model.gated_short_conv(*a) * ct),
                   argnums=(0, 1))(bcx, taps)
    # the loop is linear in the taps and in each chunk: its gradient by
    # one-hot probes of the cotangent's sum, element by element
    ctn = np.asarray(ct[0], np.float64)
    d_taps = np.zeros((d, 3))
    for i in range(3):
        one = np.zeros((d, 3))
        one[:, i] = 1.0
        d_taps[:, i] = (loop_conv(b, c, x, one) * ctn).sum(0)
    np.testing.assert_allclose(got[1], d_taps, atol=1e-4)
    d_c = loop_conv(b, np.ones_like(c), x, np.asarray(taps, np.float64)) \
        * ctn
    np.testing.assert_allclose(got[0][0, :, d:2 * d], d_c, atol=1e-4)
    eps = 1e-3
    for chunk, grid in ((0, b), (2, x)):
        probe = np.zeros_like(grid)
        probe[11, 2] = eps
        args = [b, c, x]
        args[chunk] = grid + probe
        up = (loop_conv(*args, np.asarray(taps, np.float64)) * ctn).sum()
        args[chunk] = grid - probe
        down = (loop_conv(*args, np.asarray(taps, np.float64)) * ctn).sum()
        assert abs(float(got[0][0, 11, chunk * d + 2])
                   - (up - down) / (2 * eps)) < 1e-4


def test_a_change_at_t_moves_t_to_t_plus_two_and_nothing_else():
    """Inside the convolution token ``t`` reaches ``t, t + 1, t + 2``:
    nothing before it (causal) and nothing after ``t + 2`` (no state)."""
    t, d, at = 40, 6, 17
    bcx = jax.random.normal(jax.random.key(0), (2, t, 3 * d))
    taps = jax.random.normal(jax.random.key(1), (d, 3))
    moved = bcx.at[:, at, 2 * d:].add(1.0)          # X at ``at`` alone
    gap = np.abs(np.asarray(model.gated_short_conv(moved, taps)
                            - model.gated_short_conv(bcx, taps))).max((0, 2))
    assert (gap[:at] == 0).all() and (gap[at + 3:] == 0).all()
    assert (gap[at:at + 3] > 0).all()
    # and through a whole conv layer of the model: causal, and with no
    # attention in it nothing past t + 2 either
    spec = sconv_moe_lm(**dict(TINY, layer_types=("conv",),
                               num_dense_layers=0), remat="none")
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 3)
    ids = tokens(9, rows=1)
    other = ids.copy()
    other[0, at] = (ids[0, at] + 1) % 61
    gap = np.abs(np.asarray(spec.apply_fn(params, other)
                            - spec.apply_fn(params, ids))).max((0, 2))
    assert (gap[:at] == 0).all() and (gap[at + 3:] == 0).all()
    assert gap[at] > 0


def test_attention_reads_every_earlier_token_and_no_later_one():
    spec = sconv_moe_lm(**dict(TINY, layer_types=("full_attention",),
                               num_dense_layers=0), remat="none")
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 3)
    ids = tokens(9, rows=1)
    other = ids.copy()
    other[0, 17] = (ids[0, 17] + 1) % 61
    gap = np.abs(np.asarray(spec.apply_fn(params, other)
                            - spec.apply_fn(params, ids))).max((0, 2))
    assert (gap[:17] == 0).all() and (gap[17:] > 0).all()


# ---------------------------------------------------------------------------
# the router's epsilon, the shares, the tied head over a slice
# ---------------------------------------------------------------------------

def moe_layer(seed, experts=32, held=None):
    p = init_routed_moe_params(jax.random.key(seed), 32, 12, experts,
                               experts_held=held)
    assert p["router_bias"].shape == (experts,) and "shared" not in p
    return dict(jax.tree.map(lambda a: a * GAIN, p),
                router=p["router"] * 40.0)


def share_of(params, first, count):
    return dict(params, experts=jax.tree.map(
        lambda a: a[first:first + count], params["experts"]))


def test_the_picks_weights_are_divided_by_their_sum_plus_epsilon():
    """``norm_eps``: 0 leaves the layer as it was (the weights sum to
    ``routed_scale``); with it they sum to ``S / (S + eps)``, and the layer
    is the reference's."""
    params = moe_layer(3, experts=16)
    x = jax.random.normal(jax.random.key(4), (2, 24, 32))
    plain = routed_moe_ffn(params, x, top_k=4)[0]
    tiny = routed_moe_ffn(params, x, top_k=4, norm_eps=1e-6)[0]
    large = routed_moe_ffn(params, x, top_k=4, norm_eps=1.0)[0]
    assert 0 < rel(tiny, plain) < 1e-5
    # sigmoid scores of four picks sum to between 0 and 4: eps = 1 takes
    # a fifth or more of every weight
    assert 0.2 < rel(large, plain) < 1.0
    want = ref.experts(x, params, s=settings(top_k=4))
    assert rel(tiny, want) < RTOL and rel(plain, want) > rel(tiny, want)
    g = ref.expert_weights(x, params, settings(top_k=4))
    s = jax.nn.sigmoid(x @ params["router"])
    picked = jnp.where(g > 0, s, 0.0).sum(-1)
    np.testing.assert_allclose(g.sum(-1), picked / (picked + 1e-6),
                               rtol=1e-6)
    assert int((g > 0).sum(-1).min()) == int((g > 0).sum(-1).max()) == 4


def test_the_rules_chunk_at_a_quarter_share_is_a_slices_picks():
    """What the cell measures: at four picks of which a quarter stay, the
    rule's chunk is one slice's picks (the cap, under twice the even
    load), and the even load of the step is a whole number of them: a
    call at the even load takes two chunks, one row more a third."""
    from autodist_tpu.parallel.moe import budgets_taken, chunk_rows

    rows, cap = 4 * 8192 * 4, 4096 * 4
    assert chunk_rows(rows, 8, 32, cap) == cap == 16384
    even = rows * 8 // 32
    assert even == 2 * cap
    counts = jnp.asarray([[even // 8] * 8, [even // 8] * 7 + [even // 8 + 1]])
    rungs, calls = budgets_taken(counts, rows, 32, cap)
    assert rungs[:3] == (cap, 2 * cap, 3 * cap) and rungs[-1] == rows
    assert calls.tolist()[:3] == [0, 1, 1] and int(calls.sum()) == 2


def test_the_selection_bias_chooses_and_takes_no_gradient():
    params = moe_layer(3, experts=16)
    x = jax.random.normal(jax.random.key(4), (48, 32))
    favoured = dict(params, router_bias=params["router_bias"].at[5].add(4.0))
    counts = routed_moe_ffn(favoured, x, top_k=4)[1]
    assert int(counts[5]) == 48 > int(routed_moe_ffn(params, x,
                                                     top_k=4)[1][5])
    grads = jax.grad(lambda p: jnp.sum(routed_moe_ffn(
        p, x, top_k=4, norm_eps=1e-6)[0] ** 2))(favoured)
    assert not np.asarray(grads["router_bias"]).any()
    assert np.asarray(grads["router"]).any()


def one_conv_expert_layer(seed=13, experts=32, top_k=4):
    kw = dict(TINY, layer_types=("conv",), num_dense_layers=0,
              num_experts=experts, top_k=top_k, remat="none")
    spec = sconv_moe_lm(**kw)
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), seed)
    x = jnp.take(params["embed"], tokens(seed + 1, rows=1), axis=0)
    return spec, params, x


def test_four_shares_add_up_to_the_uncut_reference_layer():
    """THE SHARE TEST.  One conv expert layer of the uncut reference (all
    32 experts of the router, 4 a token) against four chips' parts: each
    chip's ROUTED part (``routed_moe_ffn`` as the model calls it, sigmoid
    scores over all 32, the selection bias, weights normalised over all
    four picks, experts ``8 c .. 8 c + 7`` held) on the layer's normed
    stream; the mixer, the residual and the router, which every chip
    computes alike, counted ONCE.  There is no shared expert to count
    once.  They add up to the reference's layer output."""
    spec, params, x = one_conv_expert_layer()
    lp = params["layers_0"]
    s = settings(top_k=4, layer_types=("conv",), num_dense_layers=0)
    with jax.default_matmul_precision("highest"):
        whole = ref._layer(x[0], lp, "conv", False, s, None)
        stream = x[0] + ref._conv_mixer(
            ref._rms_norm(x[0], lp["ln_op"]["scale"], 1e-5), lp["conv"], s,
            None)
        normed = ref._rms_norm(stream, lp["ln_ffn"]["scale"], 1e-5)
        parts, counts = zip(*(routed_moe_ffn(
            share_of(lp["moe"], first, 8), normed, top_k=4,
            experts_held=(first, 8), norm_eps=1e-6)
            for first in (0, 8, 16, 24)))
        all_held = routed_moe_ffn(lp["moe"], normed, top_k=4,
                                  norm_eps=1e-6)[0]
    assert lp["moe"]["experts"]["w_gate"].shape[0] == 32
    assert rel(stream + sum(parts), whole) < RTOL
    # with no experts_held it is the whole layer
    assert rel(stream + all_held, whole) < RTOL
    # every pick of every token lands on exactly one chip
    assert sum(int(c.sum()) for c in counts) == 64 * 4
    # no part is a rounding error of the layer
    assert rel(whole, stream) > 1e-2
    assert all(rel(whole, whole - p) > 1e-3 for p in parts)


def test_tied_head_over_a_vocabulary_slice_is_the_references():
    """A sliced vocabulary is a smaller vocabulary: the program over rows
    ``0 .. V/4`` of a table, tied, gives the reference's loss and table
    gradient over the same rows (lookup and head both), and the untied
    program's two gradients add up to the tied one's."""
    full = sconv_moe_lm(**dict(TINY, vocab_size=64))
    table = seeded(jax.eval_shape(full.init, jax.random.key(0)),
                   5)["embed"]
    kw = dict(TINY, vocab_size=16, layer_types=("conv", "full_attention"),
              num_dense_layers=1, remat="none")
    spec = sconv_moe_lm(**kw)
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 5)
    params["embed"] = table[:16]
    batch = tokens(2, vocab=16)
    s = settings(layer_types=kw["layer_types"])
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(spec.loss_fn))(
            params, {"tokens": batch})
        want, want_grads = ref.loss_and_grads(params, jnp.asarray(batch),
                                              row_block=2, s=s)
        untied = sconv_moe_lm(**kw, tie_embedding=False)
        two = jax.jit(jax.grad(untied.loss_fn))(
            dict(params, head=table[:16]), {"tokens": batch})
    assert abs(float(loss) - float(want)) < RTOL
    assert rel(grads["embed"], want_grads["embed"]) < RTOL
    assert rel(two["embed"] + two["head"], grads["embed"]) < RTOL
    # the head's part is dense: every held row takes a gradient
    assert np.asarray(two["head"]).any(axis=1).all()


# ---------------------------------------------------------------------------
# what the checkpoints keep, and the gauges
# ---------------------------------------------------------------------------

def test_backward_runs_no_kernel_twice_and_conv_layers_keep_nothing():
    def count(remat):
        spec = sconv_moe_lm(**TINY, remat=remat, attn_fn=FLASH)
        shapes = jax.eval_shape(spec.init, jax.random.key(0))
        jaxpr = jax.make_jaxpr(jax.grad(spec.loss_fn))(
            shapes, {"tokens": tokens(0)})
        return routed_cases.primitives(jaxpr.jaxpr)["pallas_call"]

    # one attention layer: a flash forward and its fused backward
    assert count("full") == count("none") == 2


def test_gauges_count_the_causal_pairs_and_what_is_kept():
    from autodist_tpu.telemetry import registry

    registry.reset_for_testing()
    spec = sconv_moe_lm(**TINY, attn_fn=FLASH)
    jax.eval_shape(spec.loss_fn, jax.eval_shape(
        spec.init, jax.random.key(0)), {"tokens": tokens(0)})
    found = {m.labels["kind"]: m.value
             for m in registry.DEFAULT_REGISTRY.metrics()
             if m.name == "autodist_gqa_pairs_per_step"}
    # 2 sequences x 4 heads x one attention layer; the kernel's tiles of
    # 64 queries x 32 keys, both at or below the diagonal
    assert found == {"causal": 8 * 64 * 65 // 2, "computed": 8 * 64 * 64}
    kept = {m.labels["name"]: m.value
            for m in registry.DEFAULT_REGISTRY.metrics()
            if m.name == "autodist_remat_kept_bytes_per_step"}
    # o [64, 4, 8] float32 a sequence of the one attention layer; the
    # picks [128, 3] int32 of the two expert layers; nothing of a conv
    # mixer or the dense FFN
    assert kept["flash_attention/o"] == 2 * 64 * 4 * 8 * 4
    assert kept["routed_moe/chosen"] == 2 * 128 * 3 * 4
    assert set(kept) == set(model.KEPT_NAMES)
    registry.reset_for_testing()
