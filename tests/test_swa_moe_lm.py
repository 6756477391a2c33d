"""``models/swa_moe_lm.py``, the window of ``ops/flash_attention.py`` inside
a model and ``parallel/moe.py: routed_moe_ffn``'s ``router_input`` and
``activation`` against the plain reference
``benchmark/reference/smallthinker.py`` (CPU, tiny widths, seeded weights).

Tolerances as in ``test_gqa_dsa_moe_lm.py``: a CPU multiplies float32
exactly, so program and reference differ by the order of their float32
sums alone, a few 1e-6 after four layers and a backward pass; ``RTOL`` is
2e-5.  The weights are the benchmark's normal(0, 0.02) times ``GAIN``, so
that the routers' scores spread (no seed here has a token's third and
fourth score within 1e-6 of each other) and a changed window, a rotary
in the wrong layers or a router on another tensor moves the loss by 1e-3
or more.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.models.base import rotary_halves
from autodist_tpu.models.swa_moe_lm import (
    KEPT_NAMES,
    attended_pairs,
    swa_moe_lm,
)
from autodist_tpu.models.transformer import dense_selected_attention
from autodist_tpu.ops import flash_attention
from autodist_tpu.ops.flash_attention import pairs_computed
from autodist_tpu.parallel.moe import (
    budgets_taken,
    init_routed_moe_params,
    routed_moe_ffn,
    chunk_rows,
)
from benchmark.reference import smallthinker as ref

import _routed_cases as routed_cases

RTOL = 2e-5
GAIN = 8.0
WINDOW = 40
LAYOUT = (0, 1, 1, 1)
TINY = dict(vocab_size=61, num_layers=4, d_model=32, num_heads=4,
            num_kv_heads=2, head_dim=16, window=WINDOW, window_layout=LAYOUT,
            rope_layout=LAYOUT, d_expert=12, num_experts=16, top_k=3,
            seq_len=96, block_k=32, moe_slice=96)
FLASH = functools.partial(flash_attention, interpret=True, block_q=32,
                          block_k=32)


def settings(first_held=0, top_k=3, window=WINDOW, window_layout=LAYOUT,
             rope_layout=LAYOUT, train_router=True, embed_scale=1.0):
    return ref.Settings(top_k=top_k, first_held=first_held, window=window,
                        window_layout=tuple(window_layout),
                        rope_layout=tuple(rope_layout), theta=1.5e6,
                        eps=1e-6, train_router=train_router,
                        embed_scale=embed_scale)


seeded = functools.partial(routed_cases.seeded, gain=GAIN)
tokens, flat, rel = routed_cases.tokens, routed_cases.flat, routed_cases.rel


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("held,remat,chunk,train_router,attn,embed_scale", [
    (None, "none", None, True, None, 1.0),
    ((4, 4), "full", 32, False, None, 1.0),
    ((4, 4), "full", None, True, FLASH, 1.0),
    ((0, 2), "none", 32, False, FLASH, 1.0),
    ((4, 4), "full", 32, False, FLASH, 25.0),
    (None, "none", None, True, None, 0.5),
])
def test_loss_and_every_gradient_match_the_reference(held, remat, chunk,
                                                     train_router, attn,
                                                     embed_scale):
    spec = swa_moe_lm(**TINY, experts_held=held, remat=remat,
                      xent_chunk=chunk, train_router=train_router,
                      attn_fn=attn, embed_scale=embed_scale)
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 3)
    batch = tokens(5)
    s = settings(held[0] if held else 0, train_router=train_router,
                 embed_scale=embed_scale)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(spec.loss_fn))(
            params, {"tokens": batch})
        want, want_grads = ref.loss_and_grads(params, jnp.asarray(batch),
                                              row_block=2, s=s)
    assert abs(float(loss) - float(want)) < RTOL
    routed_cases.assert_every_gradient_matches(
        grads, want_grads, RTOL,
        lambda name: not train_router and "router" in name)


#: what the program computes in the stated model's place -> its kwargs
WRONG = {
    "all_keys": dict(window=96),
    "half_the_window": dict(window=WINDOW // 2),
    "twice_the_window": dict(window=2 * WINDOW),
    "rotary_in_global_layers": dict(rope_layout=(1, 1, 1, 1)),
    "no_rotary_anywhere": dict(rope_layout=(0, 0, 0, 0)),
    "window_in_every_layer": dict(window_layout=(1, 1, 1, 1)),
    "router_reads_the_stream": dict(router_before_attention=False),
    "rows_enter_four_times_as_large": dict(embed_scale=4.0),
}


def stated_weights():
    return seeded(jax.eval_shape(swa_moe_lm(**TINY).init,
                                 jax.random.key(0)), 3)


@functools.cache
def stated_loss():
    """The stated model's loss on the weights and the batch the other
    models are run on."""
    with jax.default_matmul_precision("highest"):
        return float(jax.jit(swa_moe_lm(**TINY).loss_fn)(
            stated_weights(), {"tokens": tokens(5)}))


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_another_model_is_another_loss(wrong):
    """The comparison sees each mechanism: the same weights under another
    window, a rotary in other layers or a router that reads the normed
    stream after attention give a loss 1e-3 or more from the stated
    model's; where the reference can state the other model (all but the
    router's placement) it agrees with that program."""
    batch, params, stated = {"tokens": tokens(5)}, stated_weights(), \
        stated_loss()
    other = swa_moe_lm(**dict(TINY, **WRONG[wrong]))
    with jax.default_matmul_precision("highest"):
        got = float(jax.jit(other.loss_fn)(params, batch))
        if "router_before_attention" not in WRONG[wrong]:
            want = float(ref.loss_and_grads(
                params, jnp.asarray(batch["tokens"]), row_block=2,
                s=settings(**WRONG[wrong]))[0])
            assert abs(got - want) < RTOL
    assert abs(got - stated) > 1e-3


def test_layers_are_of_two_kinds_by_index():
    """Layer 0's attention is the masked formula over ALL earlier keys
    WITHOUT rotary, layer 1's the formula over the window WITH it, and the
    two differ: read off the attention calls the model makes."""
    seen = []

    def spy(q, k, v, causal, **kw):
        seen.append((q, k, v, kw))
        return dense_selected_attention(q, k, v, causal, block_k=32, **kw)

    spec = swa_moe_lm(**dict(TINY, num_layers=2, window_layout=(0, 1),
                             rope_layout=(0, 1)), remat="none", attn_fn=spy)
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 7)
    batch = tokens(9, rows=1)
    with jax.disable_jit(), jax.default_matmul_precision("highest"):
        spec.loss_fn(params, {"tokens": batch})
    (q0, k0, _, kw0), (q1, k1, _, kw1) = seen
    assert kw0 == {} and kw1 == {"window": WINDOW}

    def projected(layer, x):
        from autodist_tpu.models.base import rms_norm
        lp = params[f"layers_{layer}"]
        h = rms_norm(x, lp["ln_attn"]["scale"], 1e-6)
        return (jnp.einsum("btd,dhk->bthk", h, lp["attn"][w])
                for w in ("wq", "wk"))

    with jax.default_matmul_precision("highest"):
        x0 = jnp.take(params["embed"], batch, axis=0)
        plain_q, plain_k = projected(0, x0)
        # the global layer: no positions at all
        assert rel(q0, plain_q) < RTOL and rel(k0, plain_k) < RTOL
        assert rel(q0, rotary_halves(plain_q, 1.5e6)) > 0.1
        # the window layer: queries and keys turned, half against half,
        # which leaves every norm and changes the numbers
        assert rel(jnp.linalg.norm(q1, axis=-1),
                   jnp.linalg.norm(rotary_halves(q1, 1.5e6), axis=-1)) < RTOL
        pos = np.arange(96)
        o_window = dense_selected_attention(q1, k1, seen[1][2], True,
                                            window=WINDOW)
        o_all = dense_selected_attention(q1, k1, seen[1][2], True)
        # rows inside the window are the causal rows; later ones are not
        assert rel(o_window[:, :WINDOW], o_all[:, :WINDOW]) < RTOL
        assert rel(o_window[:, WINDOW:], o_all[:, WINDOW:]) > 1e-2
        mask = (pos[None] <= pos[:, None]) & (pos[None] > pos[:, None]
                                              - WINDOW)
        k_rep, v_rep = (jnp.repeat(y, 2, axis=2) for y in (k1, seen[1][2]))
        s = jnp.einsum("bqhd,bkhd->bhqk", q1, k_rep) / 4.0
        want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(
            jnp.where(mask, s, -1e30), axis=-1), v_rep)
        assert rel(o_window, want) < RTOL


def test_router_reads_the_layers_input_and_hands_it_its_gradient():
    """One layer: the picks are those of ``x_l W_r`` (not of the normed
    stream after attention), and with the router's weights not trained the
    only path from the weights' scores to the loss runs through ``x_l``:
    the gradient of the router's INPUT is non-zero, that of the router's
    leaf zero, and it equals the reference's."""
    spec = swa_moe_lm(**dict(TINY, num_layers=1, window_layout=(1,),
                             rope_layout=(1,)), remat="none",
                      return_counts=True, train_router=False)
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 4)
    batch = tokens(6, rows=1)
    with jax.default_matmul_precision("highest"):
        _, aux = spec.loss_fn(params, {"tokens": batch})
        x0 = jnp.take(params["embed"], batch, axis=0)
        moe = params["layers_0"]["moe"]
        g = ref.expert_weights(x0, moe, settings(top_k=3))
        assert (np.asarray(aux["tokens_per_expert"][0])
                == np.asarray((g > 0).sum((0, 1)))).all()
        stream = swa_moe_lm(**dict(TINY, num_layers=1, window_layout=(1,),
                                   rope_layout=(1,)), remat="none",
                            return_counts=True,
                            router_before_attention=False)
        _, other = stream.loss_fn(params, {"tokens": batch})
        assert (np.asarray(other["tokens_per_expert"])
                != np.asarray(aux["tokens_per_expert"])).any()


def test_scores_pass_their_gradient_to_the_router_input_not_to_x():
    params = moe_layer(5)
    x = jax.random.normal(jax.random.key(6), (24, 32))
    entered = jax.random.normal(jax.random.key(7), (24, 32))

    def loss(x, entered, router):
        y, _ = routed_moe_ffn(dict(params, router=router), x, top_k=4,
                              scoring="softmax_of_picked", router_input=entered,
                              activation=jax.nn.relu)
        return jnp.sum(y ** 2)

    def want(x, entered, router):
        return jnp.sum(ref.experts(x, entered, dict(params, router=router),
                                   s=settings(top_k=4)) ** 2)

    got = jax.grad(loss, (0, 1, 2))(x, entered, params["router"])
    wanted = jax.grad(want, (0, 1, 2))(x, entered, params["router"])
    for g, w in zip(got, wanted):
        assert np.asarray(w).any() and rel(g, w) < RTOL
    # the same layer where the router reads what the experts read: x then
    # takes both gradients
    both = jax.grad(lambda x: jnp.sum(routed_moe_ffn(
        params, x, top_k=4, scoring="softmax_of_picked",
        activation=jax.nn.relu)[0] ** 2))(x)
    same = jax.grad(loss, (0, 1))(x, x, params["router"])
    assert rel(both, same[0] + same[1]) < RTOL
    # not trained, the router's leaf gets nothing and its input still does
    frozen = jax.grad(lambda e, r: jnp.sum(routed_moe_ffn(
        dict(params, router=r), x, top_k=4, scoring="softmax_of_picked",
        router_input=e, activation=jax.nn.relu,
        train_router=False)[0] ** 2), (0, 1))(entered, params["router"])
    assert rel(frozen[0], wanted[1]) < RTOL
    assert not np.asarray(frozen[1]).any()


@pytest.mark.parametrize("activation,other", [
    (jax.nn.relu, jax.nn.silu), (jax.nn.silu, jax.nn.relu)])
def test_the_gate_is_the_callers(activation, other):
    """ReGLU where asked, SwiGLU by default, each the plain formula on the
    picked experts; a gate input of exactly 0 takes ``relu``'s derivative
    0 in program and reference alike."""
    params = moe_layer(8)
    x = jax.random.normal(jax.random.key(9), (24, 32))
    y, _ = routed_moe_ffn(params, x, top_k=4, scoring="softmax",
                          activation=activation)
    g = ref.expert_weights(x, params, settings(top_k=4))
    w = params["experts"]
    plain = sum(g[:, e, None] * ((activation(x @ w["w_gate"][e])
                                  * (x @ w["w_up"][e])) @ w["w_down"][e])
                for e in range(16))
    assert rel(y, plain) < RTOL
    y_other, _ = routed_moe_ffn(params, x, top_k=4, scoring="softmax",
                                activation=other)
    assert rel(y_other, y) > 1e-2
    if activation is jax.nn.relu:
        assert rel(y, ref.experts(x, x, params, s=settings(top_k=4))) < RTOL
        dead = dict(params, experts=dict(w, w_gate=jnp.zeros_like(
            w["w_gate"])))
        grads = jax.grad(lambda p: jnp.sum(routed_moe_ffn(
            p, x, top_k=4, scoring="softmax",
            activation=jax.nn.relu)[0] ** 2))(dead)
        assert not np.asarray(grads["experts"]["w_gate"]).any()
    else:
        default, _ = routed_moe_ffn(params, x, top_k=4, scoring="softmax")
        assert (np.asarray(default) == np.asarray(y)).all()


@pytest.mark.parametrize("router", ["even", "collapsed"])
def test_three_session_steps_match_the_reference_adamw(router):
    """Through ``AutoDist.capture(has_aux=True) -> create_distributed_
    session -> run``: three steps' losses and the parameters after them
    against the reference under AdamW written out; the gauges of the pairs
    are set at trace time, the ``computed`` rows of the expert layers are
    the chunks the LAST STEP's calls took (4 layers, each ONE call over 2
    slices of 96 tokens x 3 picks, a quarter of them expected here: a
    chunk is 288 places, twice the even load and a slice's picks)."""
    from autodist_tpu import strategy as strategies
    from autodist_tpu.autodist import (AutoDist,
                                       _reset_default_autodist_for_testing)
    from autodist_tpu.mesh import build_mesh
    from autodist_tpu.telemetry.registry import DEFAULT_REGISTRY

    collapsed = router == "collapsed"
    first = 0 if collapsed else 4
    spec = swa_moe_lm(**TINY, experts_held=(first, 4), return_counts=True,
                      attn_fn=FLASH, train_router=not collapsed)
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
        rows, after = routed_cases.budget_gauges()
        delta = ref.flatten(ref.leaf_diff_norms(
            sess.export_state()[0], params))
    _reset_default_autodist_for_testing()
    for out, want in zip(outs, want_losses):
        assert abs(float(out["loss"]) - want) < RTOL
        assert np.asarray(out["aux"]["tokens_per_expert"]).shape == (4, 4)
    # 4 layers a step, one call each; a collapsed router (every token
    # picks experts 0, 1 and 2, all held) makes every call take both
    # chunks, an even one leaves every call inside the first
    last = {r: after.get(r, 0) - two_steps.get(r, 0) for r in rungs}
    assert sum(after.get(r, 0) - before.get(r, 0) for r in rungs) == 12
    assert rows == sum(r * n for r, n in last.items())
    assert last == {r: 4 * (r == rungs[collapsed]) for r in rungs}
    counts = np.asarray(outs[-1]["aux"]["tokens_per_expert"])
    assert budgets_taken(counts, 2 * 96 * 3, 16, 96 * 3)[1].tolist() \
        == list(last.values())
    for name, want in want_delta.items():
        assert abs(delta[name] - want) <= 1e-4 * max(want, 1e-6), name
    gauges = {(m.name, m.labels.get("kind") or m.labels.get("name")): m.value
              for m in DEFAULT_REGISTRY.metrics()
              if m.name.startswith(("autodist_swa_", "autodist_moe_rows",
                                    "autodist_remat_"))}
    # one global layer and three window layers, 4 heads, 2 rows
    attended = attended_pairs(96, None) + 3 * attended_pairs(96, WINDOW)
    assert attended_pairs(96, None) == 96 * 97 // 2
    assert attended_pairs(96, WINDOW) == sum(
        min(t + 1, WINDOW) for t in range(96))
    assert gauges[("autodist_swa_pairs_per_step", "attended")] \
        == 2 * 4 * attended
    # one q block of 96 rows: every key block from the window's first on
    computed = pairs_computed(96, block_k=32) + 3 * pairs_computed(
        96, block_k=32, window=WINDOW)
    assert gauges[("autodist_swa_pairs_per_step", "computed")] \
        == 2 * 4 * computed == 2 * 4 * 4 * 96 * 96
    assert gauges[("autodist_moe_rows_per_step", "computed")] == rows
    assert gauges[("autodist_moe_rows_per_step", "expected")] \
        == 4 * 2 * 96 * 3 / 4
    assert gauges[("autodist_remat_kept_bytes_per_step",
                   "flash_attention/o")] == 4 * 2 * 4 * 96 * 16 * 4
    assert gauges[("autodist_remat_kept_bytes_per_step",
                   "routed_moe/chosen")] == 4 * 2 * 96 * 3 * 4


def test_the_cells_pairs_are_the_issues():
    """At the cell's size: 58,722,304 pairs a head in a window layer,
    134,225,920 in a global one, 310,392,832 a period; the kernels form
    252 and 528 tiles of 512 x 512: 1.084 pairs a pair attended."""
    assert attended_pairs(16384, 4096) == 58_722_304
    assert attended_pairs(16384, None) == 134_225_920
    period = attended_pairs(16384, None) + 3 * attended_pairs(16384, 4096)
    assert period == 310_392_832
    tiles = (pairs_computed(16384) + 3 * pairs_computed(16384, window=4096))
    assert tiles == (528 + 3 * 252) * 512 * 512
    assert tiles / period == pytest.approx(1.0844, abs=1e-4)


def test_backward_runs_no_kernel_twice_and_sorts_once():
    """With the names kept, the backward of a layer holds one attention
    kernel of its own and no second forward, and the routers' top-k is not
    run again: a forward and a backward kernel a layer."""
    def count(remat):
        spec = swa_moe_lm(**TINY, remat=remat, attn_fn=FLASH)
        params = jax.eval_shape(spec.init, jax.random.key(0))
        jaxpr = jax.make_jaxpr(jax.grad(spec.loss_fn))(
            params, {"tokens": tokens(1)})
        found = routed_cases.primitives(jaxpr.jaxpr)
        return found["pallas_call"], found["top_k"]

    assert count("none") == count("full") == (8, 4)
    assert set(KEPT_NAMES) == {
        "flash_attention/o", "flash_attention/lse", "routed_moe/chosen",
        "routed_moe/order", "routed_moe/inverse", "routed_moe/sizes"}


def test_layouts_are_one_entry_a_layer():
    with pytest.raises(ValueError, match="window_layout has 3"):
        swa_moe_lm(**dict(TINY, window_layout=(0, 1, 1)))
    with pytest.raises(ValueError, match="remat"):
        swa_moe_lm(**TINY, remat="some")


# ---------------------------------------------------------------------------
# the experts: the share
# ---------------------------------------------------------------------------

def moe_layer(seed, held=None):
    p = init_routed_moe_params(jax.random.key(seed), 32, 12, 16,
                               experts_held=held, selection_bias=False)
    assert "router_bias" not in p and "shared" not in p
    return dict(jax.tree.map(lambda a: a * GAIN, p),
                router=p["router"] * 40.0)


def share_of(params, first, count):
    return dict(params, experts=jax.tree.map(
        lambda a: a[first:first + count], params["experts"]))


def test_eight_shares_add_up_to_the_whole_layer():
    """Eight chips hold 2 of 16 experts each: what each computes for its
    own experts (ReLU-gated, picked from the layer's input) adds up to the
    uncut reference's expert sum, and each share is the reference's
    share.  Everything else of the layer (the attention, the residual,
    the router) every chip computes alike and is counted once: the whole
    layer's output is the stream after attention plus the shares' sum."""
    params = moe_layer(3)
    x = jax.random.normal(jax.random.key(4), (2, 24, 32))
    entered = jax.random.normal(jax.random.key(5), (2, 24, 32))
    whole = ref.experts(x, entered, params, s=settings(top_k=4))
    parts, counts = [], []
    for chip in range(8):
        y, n = routed_moe_ffn(share_of(params, 2 * chip, 2), x, top_k=4,
                              experts_held=(2 * chip, 2),
                              scoring="softmax_of_picked",
                              router_input=entered, activation=jax.nn.relu)
        parts.append(y)
        counts.append(n)
        want = ref.experts(x, entered, share_of(params, 2 * chip, 2),
                           s=settings(2 * chip, top_k=4))
        assert rel(y, want) < RTOL
    assert rel(sum(parts), whole) < RTOL
    assert int(jnp.concatenate(counts).sum()) == 2 * 24 * 4   # every pick


def test_eight_shares_add_up_to_the_uncut_reference_layer():
    """THE SHARE TEST.  One window layer of the uncut reference (all 16
    experts) against the eight chips' parts: each chip's expert layer
    (``routed_moe_ffn`` as the model calls it: softmax over all 16, the
    layer's input for the router, ReLU gate, experts ``2c, 2c + 1`` held)
    on the layer's normed stream; the attention, the residual and the
    router, which every chip computes alike, counted once.  They add up to
    the reference's layer output."""
    kw = dict(TINY, num_layers=1, window_layout=(1,), rope_layout=(1,))
    spec = swa_moe_lm(**kw)
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 13)
    lp = params["layers_0"]
    x = jnp.take(params["embed"], tokens(14, rows=1), axis=0)[0]   # [T, D]
    s = settings(window_layout=(1,), rope_layout=(1,))
    with jax.default_matmul_precision("highest"):
        whole = ref._layer(x, lp, True, True, s, None)
        stream = x + ref._attention(
            ref._rms_norm(x, lp["ln_attn"]["scale"], 1e-6), lp["attn"],
            True, True, s, None)
        normed = ref._rms_norm(stream, lp["ln_mlp"]["scale"], 1e-6)
        parts = [routed_moe_ffn(
            share_of(lp["moe"], 2 * chip, 2), normed, top_k=3,
            experts_held=(2 * chip, 2), scoring="softmax_of_picked",
            router_input=x,
            activation=jax.nn.relu)[0] for chip in range(8)]
    assert rel(stream + sum(parts), whole) < RTOL
    # the experts' part is no rounding error of the layer
    assert rel(whole, stream) > 1e-2
    assert sum(bool(np.asarray(p).any()) for p in parts) >= 4


# ---------------------------------------------------------------------------
# a chunk is the shapes' alone, and over any chunks it is this model's layer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,held,total,cap,want", [
    (16384 * 6, 8, 64, 4096 * 6, 24576),      # this model's step
    (16384 * 6, 16, 128, 4096 * 6, 24576),    # kanana's four sequences
    (16384 * 8, 16, 128, 4096 * 8, 32768),    # keye's step
    (16384 * 6, 64, 64, 4096 * 6, 24576),     # every expert held: a slice's
])
def test_chunk_rows_are_the_shapes_alone(rows, held, total, cap, want):
    """Twice the rows an even router sends here, no more than a slice's
    picks, in whole 512-row tiles: no argument, key or model's name
    chooses a chunk (PR 34's rungs, PR 39's chunks), this model's cell
    included; and at the cells' shapes it is no wider than the parent's
    top rung a slice."""
    assert chunk_rows(rows, held, total, cap) == want <= cap


@pytest.mark.parametrize("load", [32, 64, 128])
def test_over_any_chunks_it_is_the_relu_gated_layer_of_the_reference(load):
    """``load`` picks routed to the two held experts fill one, two and all
    four chunks: over each the layer as this model calls it (the router
    reads ANOTHER tensor than the experts, ReLU gate, softmax over the
    picked logits) is the reference's, value and every gradient."""
    params = routed_cases.layer("softmax")
    entered = routed_cases.tokens_routing(load)
    x = jax.random.normal(jax.random.key(41), entered.shape)
    s = settings(first_held=routed_cases.HELD[0], top_k=routed_cases.TOP_K)

    def program(p, x, entered):
        y, counts = routed_moe_ffn(
            p, x, top_k=routed_cases.TOP_K, experts_held=routed_cases.HELD,
            scoring="softmax_of_picked", router_input=entered,
            activation=jax.nn.relu)
        return jnp.sum(y ** 2), counts

    def reference(p, x, entered):
        return jnp.sum(ref.experts(x, entered, p, s=s) ** 2)

    (got, counts), grads = jax.value_and_grad(
        program, (0, 1, 2), has_aux=True)(params, x, entered)
    want, want_grads = jax.value_and_grad(reference, (0, 1, 2))(
        params, x, entered)
    rungs, calls = budgets_taken(counts, entered.shape[0]
                                 * routed_cases.TOP_K, routed_cases.TOTAL)
    assert rungs == routed_cases.RUNGS and int(counts.sum()) == load
    assert calls.tolist() == [int(r == load) for r in rungs]
    assert load // routed_cases.CHUNK == routed_cases.LOADS[load]
    assert rel(got, want) < RTOL
    for name, leaf in flat(want_grads[0]).items():
        assert rel(flat(grads[0])[name], leaf) < RTOL, name
    assert rel(grads[1], want_grads[1]) < RTOL
    assert rel(grads[2], want_grads[2]) < RTOL


@pytest.mark.parametrize("load", [16, 32, 64])
def test_rows_past_the_last_group_are_never_read(load):
    routed_cases.assert_unwritten_rows_are_never_read("softmax_of_picked",
                                                      load)


@pytest.mark.parametrize("top_k", [6, 8])
def test_nothing_is_as_wide_as_the_picks(top_k):
    routed_cases.assert_nothing_is_as_wide_as_the_picks("softmax_of_picked",
                                                        top_k)


# ---------------------------------------------------------------------------
# the router without the softmax over all experts
# ---------------------------------------------------------------------------

def test_softmax_of_picked_is_the_softmax_router():
    """The top-k logits are the top-k of the softmax and a softmax over
    them is ``p_e / sum_S p_j``: the same layer and the same gradients as
    ``scoring="softmax"`` wherever float32 tells the probabilities
    apart."""
    params = moe_layer(31)
    x = jax.random.normal(jax.random.key(32), (48, 32))

    def run(scoring):
        def loss(p, x):
            y, counts = routed_moe_ffn(p, x, top_k=4, scoring=scoring,
                                       activation=jax.nn.relu)
            return jnp.sum(y ** 2), counts
        return jax.value_and_grad(loss, (0, 1), has_aux=True)(params, x)

    (want, want_counts), want_grads = run("softmax")
    (got, counts), grads = run("softmax_of_picked")
    assert (np.asarray(counts) == np.asarray(want_counts)).all()
    assert rel(got, want) < RTOL
    for name, leaf in flat(want_grads[0]).items():
        assert rel(flat(grads[0])[name], leaf) < RTOL, name
    assert rel(grads[1], want_grads[1]) < RTOL


def test_underflow_makes_no_tie():
    """Logits 200 apart: ``softmax`` underflows every probability but the
    largest to 0, a selection by probability is then a tie that goes to
    the lowest-numbered experts (0, 1, 2: whatever the logits say), and a
    selection by the logits picks the experts the router ranks highest; the
    reference does the same."""
    params = moe_layer(33)
    order = np.random.RandomState(0).permutation(16)      # 13 ranks first
    router = np.zeros((32, 16), np.float32)
    router[0] = -200.0 * np.argsort(order)
    params = dict(params, router=jnp.asarray(router))
    x = jnp.asarray(np.random.RandomState(1).rand(24, 32) + 1.0, jnp.float32)
    _, by_p = routed_moe_ffn(params, x, top_k=4, scoring="softmax",
                             activation=jax.nn.relu)
    y, by_r = routed_moe_ffn(params, x, top_k=4, scoring="softmax_of_picked",
                             activation=jax.nn.relu)
    top = sorted(order[:4].tolist())
    assert np.flatnonzero(np.asarray(by_r)).tolist() == top
    assert np.flatnonzero(np.asarray(by_p)).tolist() != top
    # the first pick takes all of the weight: the others' underflow to 0
    g = np.asarray(ref.expert_weights(x, params, settings(top_k=4)))
    assert (g[:, int(order[0])] == 1.0).all() and g.sum() == len(x)
    assert rel(y, ref.experts(x, x, params, s=settings(top_k=4))) < RTOL
