"""``models/mla_moe_lm.py`` and ``parallel/moe.py: routed_moe_ffn`` against
the plain reference ``benchmark/reference/deepseek_v3.py`` (CPU, tiny
widths, seeded weights).

Tolerances.  A CPU multiplies float32 exactly, so the program and the
reference differ by the ORDER of their float32 sums alone (a gather and a
grouped product against a masked sum over all tokens, a fused attention
against row blocks): relative 1e-6 a product, a few 1e-6 after three
layers and a backward pass.  ``RTOL`` 2e-5 is ten times what they read
(1e-7 to 2e-6); anything left out (an expert's pick, a shared expert, the
rotary turn, a norm) moves a leaf by 1e-2 or more.  No seed here has two
scores within 1e-6 of a tie at the sixth place, so the selection itself is
the same on both sides.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.models import routed_decoder
from autodist_tpu.models.base import _rotary, rms_norm, rotary_halves
from autodist_tpu.models.mla_moe_lm import (
    KEPT_NAMES,
    attention_operands,
    latent_attention,
    mla_moe_lm,
)
from autodist_tpu.models.routed_decoder import equations
from autodist_tpu.models.transformer import dense_attention
from autodist_tpu.ops import flash_attention
from autodist_tpu.parallel.moe import (
    ROUTING_RESIDUAL_NAMES,
    init_routed_moe_params,
    budgets_taken,
    chunk_rows,
    routed_moe_ffn,
    routed_rows,
)
from benchmark.reference import deepseek_v3 as ref

import _routed_cases as routed_cases

RTOL = 2e-5
TINY = dict(vocab_size=61, num_layers=3, first_dense=1, d_model=32,
            num_heads=2, qk_nope=8, qk_rope=4, v_head=8, kv_lora=16,
            d_ff=48, d_expert=12, num_experts=16, top_k=3,
            shared_experts=2, seq_len=32, attn_fn=dense_attention)


def settings(first_held=0, top_k=3, train_router=True):
    return ref.Settings(top_k=top_k, routed_scale=2.448,
                        first_held=first_held, qk_nope=8, theta=1e6,
                        eps=1e-6, train_router=train_router)


seeded = routed_cases.seeded
tokens = functools.partial(routed_cases.tokens, rows=4, t=32, generator=True)


def rotary(x, theta):
    """Rotary positions on INTERLEAVED pairs ``(x[2i], x[2i+1])``: what
    kanana's weights' columns mean, and what the program computed up to PR
    29.  The model turns ``rotary_halves`` of the same columns
    de-interleaved; no caller in the program."""
    return _rotary(x, theta, (-1, 2))


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


@pytest.mark.parametrize("held,remat,chunk,train_router", [
    # a share, rematerialised, chunked head, routers not trained: the
    # benchmark's configuration at test size
    ((4, 4), "full", 32, False),
    (None, "none", None, True),      # all 16 experts held, plain head
    ((0, 16), "full", None, True),   # every expert held from the first
])
def test_loss_and_every_gradient_match_the_reference(held, remat, chunk,
                                                     train_router):
    spec = mla_moe_lm(**TINY, experts_held=held, remat=remat,
                      xent_chunk=chunk, train_router=train_router)
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 7)
    batch = tokens(1)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(spec.loss_fn))(
            params, {"tokens": batch})
        want_loss, want = ref.loss_and_grads(
            params, jnp.asarray(batch), row_block=2,
            s=settings(held[0] if held else 0, train_router=train_router))
    assert abs(float(loss) - float(want_loss)) < RTOL
    got = routed_cases.assert_every_gradient_matches(
        grads, want, RTOL, lambda name: name.endswith("router_bias") or (
            name.endswith("/router") and not train_router))   # selects
    assert len(got) == 43


def test_rotary_turns_interleaved_pairs_and_keeps_products_relative():
    x = jax.random.normal(jax.random.key(0), (1, 6, 2, 4))
    y = rotary(x, 1e6)
    np.testing.assert_allclose(y[:, 0], x[:, 0], atol=1e-7)   # position 0
    np.testing.assert_allclose(                # a turn keeps every pair's norm
        jnp.sum(y.reshape(1, 6, 2, 2, 2) ** 2, -1),
        jnp.sum(x.reshape(1, 6, 2, 2, 2) ** 2, -1), rtol=1e-5)
    # q_s . k_t depends on t - s alone
    q = jnp.broadcast_to(x[:, :1], x.shape)
    dots = jnp.einsum("bshd,bthd->bhst", rotary(q, 1e6), rotary(q, 1e6))
    np.testing.assert_allclose(dots[0, 0, 0, 2], dots[0, 0, 3, 5],
                               rtol=1e-5)
    np.testing.assert_allclose(ref._rotary(x, 1e6), y, atol=1e-6)


# ---------------------------------------------------------------------------
# the attention's products write the kernel's operands (PR 30)
# ---------------------------------------------------------------------------
def parent_latent_attention(p, x, attn_fn, *, qk_nope, qk_rope, theta, eps):
    """Latent attention as it was written up to PR 29: one product a
    weight, its activation sliced after, interleaved ``rotary``.  The
    definition the rewritten one is held to."""
    heads, latent = p["wq"].shape[1], p["wkv_b"].shape[0]
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    q = jnp.concatenate(
        [q[..., :qk_nope], rotary(q[..., qk_nope:], theta)], axis=-1)
    kv_a = x @ p["wkv_a"]
    c = rms_norm(kv_a[..., :latent], p["kv_norm"]["scale"], eps)
    k_rope = rotary(kv_a[..., latent:], theta)
    kv = jnp.einsum("btc,chk->bthk", c, p["wkv_b"])
    k = jnp.concatenate(
        [kv[..., :qk_nope],
         jnp.broadcast_to(k_rope[:, :, None, :],
                          k_rope.shape[:2] + (heads, qk_rope))], axis=-1)
    return jnp.einsum("bthv,hvd->btd",
                      attn_fn(q, k, kv[..., qk_nope:], True), p["wo"])


# distinct sizes everywhere, so that a shape names its array
MLA = dict(d=40, heads=4, nope=24, rope=8, dv=16, latent=48, t=32)


def attention_leaves(seed, w=MLA):
    def normal(k, *shape):
        return jax.random.normal(k, shape) * 0.3

    k = jax.random.split(jax.random.key(seed), 5)
    return {"wq": normal(k[0], w["d"], w["heads"], w["nope"] + w["rope"]),
            "wkv_a": normal(k[1], w["d"], w["latent"] + w["rope"]),
            "kv_norm": {"scale": 1.0 + normal(k[2], w["latent"])},
            "wkv_b": normal(k[3], w["latent"], w["heads"],
                            w["nope"] + w["dv"]),
            "wo": normal(k[4], w["heads"], w["dv"], w["d"])}


def test_rewritten_attention_is_the_parents_in_outputs_and_gradients():
    """Outputs and the gradient of every leaf, under the leaves' own names
    and shapes, within 1e-6 relative of the parent's formulation: the same
    dot products over the same terms, the rope columns in another order
    on queries and keys alike."""
    p = attention_leaves(0)
    x = jax.random.normal(jax.random.key(1), (2, MLA["t"], MLA["d"]))
    ct = jax.random.normal(jax.random.key(2), x.shape)
    kw = dict(theta=1e4, eps=1e-6)

    def new(p, x):
        return latent_attention(attention_operands(p, MLA["nope"]), x,
                                dense_attention, **kw)

    def old(p, x):
        return parent_latent_attention(
            p, x, dense_attention, qk_nope=MLA["nope"], qk_rope=MLA["rope"],
            **kw)

    with jax.default_matmul_precision("highest"):
        got, want = new(p, x), old(p, x)
        g_got = jax.grad(lambda p, x: jnp.vdot(new(p, x), ct), (0, 1))(p, x)
        g_want = jax.grad(lambda p, x: jnp.vdot(old(p, x), ct), (0, 1))(p, x)
    assert rel(got, want) < 1e-6
    got, want = ref._flat(g_got[0], np.asarray), ref._flat(g_want[0],
                                                            np.asarray)
    assert {k: v.shape for k, v in got.items()} \
        == {k: v.shape for k, v in ref._flat(p, np.asarray).items()}
    for name in want:
        assert rel(got[name], want[name]) < 1e-6, name
    assert rel(g_got[1], g_want[1]) < 1e-6


@pytest.mark.parametrize("shape", [(1, 6, 2, 8), (2, 5, 12)])
def test_half_split_rotary_is_rotary_of_the_interleaved_columns(shape):
    """De-interleave, turn half against half: ``rotary`` of the interleaved
    columns after the same permutation, to the bit."""
    x = jax.random.normal(jax.random.key(3), shape)
    r = shape[-1]
    order = np.concatenate([np.arange(0, r, 2), np.arange(1, r, 2)])
    np.testing.assert_array_equal(rotary_halves(x[..., order], 1e4),
                                  rotary(x, 1e4)[..., order])


def layer_gradient_jaxpr(remat, **sizes):
    spec = mla_moe_lm(**dict(TINY, **sizes), experts_held=(4, 4),
                      remat=remat)
    params = jax.eval_shape(spec.init, jax.random.key(0))
    return params, jax.make_jaxpr(jax.grad(spec.loss_fn))(
        params, {"tokens": tokens(0, rows=2)}).jaxpr


def test_gradient_holds_no_pair_dimension_and_slices_no_kv_activation():
    """One dense and one expert layer: no intermediate whose minor
    dimension is a pair, and nothing cut from (or padded back to) an
    activation as wide as ``wkv_b``'s or ``wq``'s product."""
    sizes = dict(num_layers=2, num_heads=4, qk_nope=24, qk_rope=8,
                 v_head=16, kv_lora=20)
    _, jaxpr = layer_gradient_jaxpr("full", **sizes)
    t, heads = TINY["seq_len"], 4
    whole = {(1, t, heads, 24 + 16), (1, t, heads, 24 + 8)}
    for eqn in equations(jaxpr):
        for var in eqn.outvars:
            assert not (var.aval.ndim >= 3 and var.aval.shape[-1] == 2), eqn
        if eqn.primitive.name in ("slice", "pad"):    # a cut, or its transpose
            assert not whole.intersection(
                {eqn.invars[0].aval.shape, eqn.outvars[0].aval.shape}), eqn
    # the parent's formulation trips both
    p = jax.eval_shape(lambda: attention_leaves(0))
    old = jax.make_jaxpr(lambda p, x: parent_latent_attention(
        p, x, dense_attention, qk_nope=24, qk_rope=8, theta=1e4,
        eps=1e-6))(p, jnp.zeros((1, 32, 40))).jaxpr
    shapes = [v.aval.shape for e in equations(old) for v in e.outvars]
    assert any(s[-1] == 2 for s in shapes if len(s) >= 3)
    assert any(e.primitive.name == "slice"
               and e.invars[0].aval.shape == (1, 32, 4, 24 + 16)
               for e in equations(old))


def inside_and_outside_the_map(jaxpr, inside=False):
    """``(equation, under a scan)`` for every equation of a jaxpr."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from inside_and_outside_the_map(
                inner, inside or eqn.primitive.name == "scan")


@pytest.mark.parametrize("remat", ["none", "full"])
def test_weights_are_cut_once_a_layer_outside_the_mapped_body(remat):
    """Outside the body mapped over sequences the forward cuts each
    attention weight twice a layer (``wq``: nope, rope; ``wkv_a``: latent,
    rope; ``wkv_b``: keys, values) and de-interleaves the two rope parts,
    and the backward pads the parts' gradients back, once.  Inside the
    body (and its transpose) nothing is de-interleaved, and ``wq`` and
    ``wkv_b`` never appear whole (``wkv_a`` stays one product)."""
    params, jaxpr = layer_gradient_jaxpr(remat)
    attn = params["layers_0"]["attn"]
    whole = {attn[name].shape for name in ("wq", "wkv_a", "wkv_b")}
    assert len(whole) == 3
    cuts, strided, joins = 0, 0, 0
    for eqn, mapped in inside_and_outside_the_map(jaxpr):
        stride = eqn.primitive.name == "slice" and any(
            s > 1 for s in eqn.params["strides"] or ())
        if mapped:
            shapes = {v.aval.shape for v in eqn.invars + eqn.outvars}
            assert not stride and not shapes.intersection(
                {attn["wq"].shape, attn["wkv_b"].shape}), eqn
        elif eqn.primitive.name == "slice":
            cuts += eqn.invars[0].aval.shape in whole
            strided += stride
        elif eqn.primitive.name == "pad":
            joins += eqn.outvars[0].aval.shape in whole
    layers = TINY["num_layers"]
    assert (cuts, strided, joins) == (6 * layers, 4 * layers, 6 * layers)


def moe_layer(seed, held_count=16, d=32, f=12, total=16):
    params = init_routed_moe_params(jax.random.key(seed), d, f, total,
                                    experts_held=held_count, d_shared=2 * f)
    return jax.tree_util.tree_map(lambda a: a * 8.0, params)   # lively


def share_of(params, first, count):
    """The leaves one chip of the deployment holds: all of the router and
    of the shared experts, ``count`` of the experts."""
    return dict(params, experts=jax.tree_util.tree_map(
        lambda a: a[first:first + count], params["experts"]))


def test_eight_shares_add_up_to_the_whole_layer():
    """Eight chips hold 2 of 16 experts each.  What each computes for its
    own experts, with the shared experts (which every chip computes alike)
    counted once, adds up to the uncut reference's layer output."""
    params = moe_layer(3)
    x = jax.random.normal(jax.random.key(4), (2, 24, 32))
    whole = ref.moe_ffn(x, params, s=settings(top_k=4))
    shared = ref._swiglu(x, params["shared"], None)
    parts, counts = [], []
    for chip in range(8):
        y, n = routed_moe_ffn(share_of(params, 2 * chip, 2), x, top_k=4,
                              experts_held=(2 * chip, 2),
                              routed_scale=2.448)
        parts.append(y - shared)
        counts.append(n)
        # and each share is the reference's share
        want = ref.moe_ffn(x, share_of(params, 2 * chip, 2),
                           s=settings(2 * chip, top_k=4))
        assert rel(y, want) < RTOL
    assert rel(sum(parts) + shared, whole) < RTOL
    assert int(jnp.concatenate(counts).sum()) == 2 * 24 * 4   # every pick


@pytest.mark.parametrize("where", ["all_on_held", "none_on_held"])
def test_no_token_is_dropped_whatever_the_routing(where):
    """A selection bias that sends every pick of every token to the held
    experts fills all ``N * k`` rows of the grouped products, and nothing
    is dropped; one that sends every pick elsewhere leaves the routed part
    zero.  Both match the reference."""
    params = moe_layer(5, held_count=4)
    bias = jnp.full((16,), -10.0).at[4:8].set(10.0)
    if where == "none_on_held":
        bias = -bias
    params["router_bias"] = bias
    x = jax.random.normal(jax.random.key(6), (48, 32))
    y, counts = routed_moe_ffn(params, x, top_k=3, experts_held=(4, 4),
                               routed_scale=2.448)
    want = ref.moe_ffn(x, params, s=settings(4))
    assert rel(y, want) < RTOL
    if where == "all_on_held":
        assert int(counts.sum()) == 48 * 3 == routed_rows(48, 3, 4, 16)[0]
        assert int(counts.min()) > 0
    else:
        assert int(counts.sum()) == 0
        assert rel(y, ref._swiglu(x, params["shared"], None)) < RTOL
    # the gradient through the gathers comes back to every token
    g = jax.grad(lambda x: jnp.sum(routed_moe_ffn(
        params, x, top_k=3, experts_held=(4, 4),
        routed_scale=2.448)[0] ** 2))(x)
    g_ref = jax.grad(lambda x: jnp.sum(
        ref.moe_ffn(x, params, s=settings(4)) ** 2))(x)
    assert rel(g, g_ref) < RTOL


def test_routed_layer_refuses_a_share_its_leaves_do_not_hold():
    params = moe_layer(1, held_count=4)
    # and the model a table of policies it no longer has, or no expert layer
    with pytest.raises(ValueError, match="remat='dots'"):
        mla_moe_lm(**TINY, remat="dots")
    with pytest.raises(ValueError, match="3 dense layers of 3"):
        mla_moe_lm(**dict(TINY, first_dense=3))
    x = jnp.zeros((8, 32))
    with pytest.raises(ValueError, match="experts_held"):
        routed_moe_ffn(params, x, top_k=3, experts_held=(0, 8))
    with pytest.raises(ValueError, match="experts_held"):
        routed_moe_ffn(params, x, top_k=3, experts_held=(14, 4))


@pytest.mark.parametrize("router", ["even", "skewed"])
def test_three_session_steps_match_the_reference_adamw(router):
    """Through ``AutoDist.capture(has_aux=True) -> create_distributed_
    session -> run``: three steps' losses and the parameters after them
    against the reference's gradients under AdamW written out; the
    per-expert token counts come back with every step; the layer's gauges
    are set when it is traced, but the ``computed`` rows: those are the
    chunks the LAST STEP's calls took, carried out of the step when it
    runs.  An even router leaves every call (2 expert layers, each ONE
    call over 4 sequences of 32 tokens x 3 picks, an eighth of them
    expected here as in the cells: 4 of 32 experts held) inside its first
    chunk of 96 places (twice the even load, and a sequence's picks); a
    selection bias that sends every pick to the held experts makes every
    call take all four chunks."""
    from autodist_tpu import strategy as strategies
    from autodist_tpu.autodist import (AutoDist,
                                       _reset_default_autodist_for_testing)
    from autodist_tpu.mesh import build_mesh
    from autodist_tpu.telemetry.registry import DEFAULT_REGISTRY

    spec = mla_moe_lm(**dict(TINY, num_experts=32), experts_held=(4, 4),
                      return_counts=True)
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 11)
    if router == "skewed":
        for i in range(TINY["first_dense"], TINY["num_layers"]):
            params[f"layers_{i}"]["moe"]["router_bias"] = jnp.full(
                (32,), -10.0).at[4:8].set(10.0)
    batches = [jnp.asarray(tokens(20 + i)) for i in range(3)]
    assert chunk_rows(4 * 32 * 3, 4, 32, 32 * 3) == 96 \
        == chunk_rows(4 * 32 * 3, 4, 32)
    rungs = (96, 192, 288, 384)
    with jax.default_matmul_precision("highest"):
        want_losses, _, want_delta, _ = ref.train_steps(
            params, batches, row_block=4, s=settings(4))

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
        counts = np.asarray(out["aux"]["tokens_per_expert"])
        assert counts.shape == (2, 4)          # expert layers x held
        assert 0 < counts.sum() <= 2 * 4 * 32 * 3
    # AdamW's first steps move every element by about lr whatever its
    # gradient, so the norms of the change agree far closer than RTOL
    for name, want in want_delta.items():
        assert abs(delta[name] - want) <= 1e-4 * max(want, 1e-6), name
    gauges = {(m.name, m.labels.get("kind")): m.value
              for m in DEFAULT_REGISTRY.metrics()
              if m.name.startswith("autodist_moe_")}
    assert gauges[("autodist_moe_experts_held", None)] == 4
    assert gauges[("autodist_moe_experts_total", None)] == 32
    computed, expected = routed_rows(4 * 32, 3, 4, 32)
    assert gauges[("autodist_moe_rows_per_step", "expected")] == 2 * expected
    # 2 expert layers a step, one call each, all taking as many chunks
    taken = rungs[3 * (router == "skewed")]
    last = {r: three_steps[r] - two_steps.get(r, 0) for r in rungs}
    assert last == {r: 2 * (r == taken) for r in rungs}
    # the row tiles (of 32: chunks of 96) ONE of the forward's grouped
    # products visited in the last step, and those that held a routed row
    assert routed_cases.tiles_gauges() == routed_cases.tiles_written_out(
        outs[-1]["aux"]["tokens_per_expert"], rungs[0])
    assert {r: three_steps[r] - before.get(r, 0) for r in rungs} \
        == {r: 6 * (r == taken) for r in rungs}
    assert rows == 2 * taken
    assert rows / (2 * expected) == (8.0 if router == "skewed" else 2.0)
    assert computed == rungs[-1]            # every chunk is every pick
    # the same rule on the integers the step returned
    assert budgets_taken(counts, 4 * 32 * 3, 32, 32 * 3)[1].tolist() \
        == [2 * (r == taken) for r in rungs]


# ---------------------------------------------------------------------------
# chunks of the sorted order (PR 39; a ladder of row budgets from PR 34);
# the softmax router's cases are in test_gqa_dsa_moe_lm.py
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("load", sorted(routed_cases.LOADS))
def test_chunks_equal_one_wide_chunk_to_the_bit(load):
    """Value and every gradient over the chunks a load takes against one
    chunk as wide as every pick (the path before the chunks) and against
    the layer written out: none routed here, inside the first chunk, a
    load of exactly a chunk and of one row more, every pick routed here."""
    routed_cases.assert_chunks_equal_one_wide_chunk("sigmoid", load)


@pytest.mark.parametrize("load", [16, 40, 100])
def test_compiled_chunks_match_the_reference(load):
    """Jitted, over one, two and four chunks, the layer and the gradient
    through it match the plain reference."""
    params = routed_cases.layer("sigmoid")
    x = routed_cases.tokens_routing(load)
    s = ref.Settings(top_k=routed_cases.TOP_K, routed_scale=2.448,
                     first_held=routed_cases.HELD[0], qk_nope=8, theta=1e6,
                     eps=1e-6, train_router=True)
    value, counts, grads = jax.jit(
        lambda p, x: routed_cases.value_and_gradients(p, x, "sigmoid"))(
        params, x)
    assert int(counts.sum()) == load
    want, want_grads = jax.value_and_grad(
        lambda p, x: jnp.sum(ref.moe_ffn(x, p, s=s) ** 2), argnums=(0, 1))(
        params, x)
    assert rel(value, want) < RTOL
    got, want = ref._flat(grads[0], np.asarray), ref._flat(want_grads[0],
                                                           np.asarray)
    for name in want:
        if not name.endswith("router_bias"):
            assert rel(got[name], want[name]) < RTOL, name
    assert rel(grads[1], want_grads[1]) < RTOL


def test_gradient_holds_one_loop_a_direction_and_fills_no_rows():
    routed_cases.assert_gradient_loops_once_a_direction_and_fills_no_rows(
        "sigmoid")


@pytest.mark.parametrize("load", [16, 32, 64])
def test_rows_past_the_last_group_are_never_read(load):
    routed_cases.assert_unwritten_rows_are_never_read("sigmoid", load)


@pytest.mark.parametrize("top_k", [6, 8])
def test_nothing_is_as_wide_as_the_picks(top_k):
    routed_cases.assert_nothing_is_as_wide_as_the_picks("sigmoid", top_k)


@pytest.mark.parametrize("rows,held,total,cap,want", [
    (16384 * 6, 16, 128, 4096 * 6, 24576),  # the kanana cell's call
    (16384 * 8, 16, 128, 4096 * 8, 32768),  # the keye cell's
    (16384 * 6, 8, 64, 4096 * 6, 24576),    # the smallthinker cell's
    (96, 4, 16, None, 48),                  # twice the even load
    (96, 16, 16, None, 96), (96, 8, 16, None, 96),  # all held, half held
    (144, 2, 16, None, 40),                 # whole 8-row tiles
    (4096 * 6, 2, 256, None, 512),          # whole 512-row tiles
    (4096 * 6, 64, 256, 1000, 1024),        # the cap in whole tiles too
])
def test_chunk_rows_follow_the_shapes_alone(rows, held, total, cap, want):
    assert chunk_rows(rows, held, total, cap) == want
    rungs, calls = budgets_taken(jnp.zeros((3, held), jnp.int32), rows,
                                 total, cap)
    assert rungs[0] == want and rungs[-1] >= rows > rungs[-1] - want
    assert calls.tolist() == [3] + [0] * (len(rungs) - 1)


def test_a_whole_batch_call_equals_its_slices_run_one_by_one():
    """One call over ``[4, 16, d]`` (what a model hands the layer since PR
    39) against the same tokens a slice a call (what the parent's
    ``lax.map`` ran): the value, the counts' sum and the gradients of the
    tokens and of every leaf, within the order of the sums."""
    params = moe_layer(3, held_count=4)
    x = jax.random.normal(jax.random.key(5), (4, 16, 32))

    def call(p, x):
        return routed_moe_ffn(p, x, top_k=3, experts_held=(4, 4),
                              routed_scale=2.448)

    def whole(p, x):
        y, counts = call(p, x)
        return jnp.sum(y ** 2), (y, counts)

    def sliced(p, x):
        y, counts = jax.lax.map(lambda part: call(p, part), x)
        return jnp.sum(y ** 2), (y, counts.sum(axis=0))

    got, want = (jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(
        params, x) for f in (whole, sliced))
    assert got[0][1][0].shape == x.shape
    np.testing.assert_array_equal(got[0][1][1], want[0][1][1])
    assert chunk_rows(4 * 16 * 3, 4, 16, 16 * 3) == 48
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        if "router_bias" not in jax.tree_util.keystr(path):
            assert rel(a, b) < RTOL, jax.tree_util.keystr(path)


# ---------------------------------------------------------------------------
# what the per-layer checkpoint keeps by name (PR 28)
# ---------------------------------------------------------------------------
FLASH = functools.partial(flash_attention, interpret=True)
TINY_FLASH = dict(TINY, attn_fn=FLASH)
ROUTED = TINY["num_layers"] - TINY["first_dense"]


def remat_model(remat, monkeypatch, **kw):
    """``remat`` "bare" is what "full" was before the names: each half
    recomputed whole (a policy that keeps nothing by name)."""
    if remat == "bare":
        monkeypatch.setattr(
            routed_decoder, "save_only_these_names",
            lambda *names: jax.checkpoint_policies.nothing_saveable)
        remat = "full"
    return mla_moe_lm(**dict(TINY_FLASH, **kw), experts_held=(4, 4),
                      remat=remat)


def count_primitives(jaxpr, names):
    found = routed_cases.primitives(jaxpr)
    return {name: found[name] for name in names}


@pytest.mark.parametrize("remat,kernels,selects", [
    ("full", 2, 1), ("none", 2, 1), ("bare", 3, 2)])
def test_backward_runs_kernel_selection_and_sorts_once_a_layer(
        remat, kernels, selects, monkeypatch):
    """The gradient's jaxpr: a forward and a backward attention kernel a
    layer, one ``top_k`` and two sorts of the picks a routed layer.  A
    checkpoint that keeps nothing by name runs the forward kernel, the
    selection and both sorts a second time.  Three more sorts a routed
    layer under any policy move one number a pick between the picks' order
    and the sorted order (the weights forward and again in the backward,
    their cotangent back): sorts of pairs where a gather of single numbers
    would be (PR 39)."""
    spec = remat_model(remat, monkeypatch)
    params = jax.eval_shape(spec.init, jax.random.key(0))
    jaxpr = jax.make_jaxpr(jax.grad(spec.loss_fn))(
        params, {"tokens": tokens(0, rows=2)})
    assert count_primitives(jaxpr.jaxpr, ("pallas_call", "top_k", "sort")) \
        == {"pallas_call": kernels * TINY["num_layers"],
            "top_k": selects * ROUTED, "sort": (2 * selects + 3) * ROUTED}


@pytest.mark.parametrize("remat", ["full"])
def test_keeping_by_name_changes_no_number(remat, monkeypatch):
    """Loss and every gradient equal the whole-layer recomputation's to
    the bit: the backward is handed the ``o``, ``lse`` and picks it would
    have recomputed.  Against no rematerialisation at all the loss and
    every leaf are equal too, but for the RMSNorm scales before attention
    and FFN and (since PR 30 cut the product after it in two) on the
    latent: XLA's CPU backend fuses their sum over tokens with the
    recomputed norm in another order (a few 1e-7, under any policy)."""
    batch = {"tokens": tokens(2, rows=2)}
    out = {}
    for which in (remat, "none", "bare"):
        spec = remat_model(which, monkeypatch)
        params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 5)
        loss, grads = jax.jit(jax.value_and_grad(spec.loss_fn))(params,
                                                               batch)
        out[which] = dict(ref._flat(grads, np.asarray), loss=loss)
    for name, got in out[remat].items():
        np.testing.assert_array_equal(got, out["bare"][name], name)
        if name.endswith(("ln_attn/scale", "ln_mlp/scale",
                          "kv_norm/scale")):
            assert rel(got, out["none"][name]) < 1e-6, name
        else:
            np.testing.assert_array_equal(got, out["none"][name], name)


@pytest.mark.parametrize("remat,attn", [
    ("full", FLASH), ("full", dense_attention), ("none", FLASH)])
def test_kept_bytes_gauge_reads_what_the_tagged_shapes_give(remat, attn,
                                                            monkeypatch):
    """``autodist_remat_kept_bytes_per_step{name}``, set when the model is
    traced: the shapes the trace tags, times sequences, times layers.  An
    attention that tags nothing keeps nothing; no checkpoint, nothing."""
    from autodist_tpu.telemetry.registry import DEFAULT_REGISTRY

    rows, t, k = 2, TINY["seq_len"], TINY["top_k"]
    spec = remat_model(remat, monkeypatch, attn_fn=attn)
    jax.eval_shape(spec.loss_fn, jax.eval_shape(spec.init, jax.random.key(0)),
                   {"tokens": tokens(0, rows=rows)})
    # (the registry is the process's: another model's names may be there)
    got = {m.labels["name"]: m.value for m in DEFAULT_REGISTRY.metrics()
           if m.name == "autodist_remat_kept_bytes_per_step"
           and m.labels["name"] in KEPT_NAMES}
    layers = rows * TINY["num_layers"] * (attn is FLASH)
    picks = rows * ROUTED * t * k * 4
    want = dict(zip(KEPT_NAMES, (
        layers * TINY["num_heads"] * t * TINY["v_head"] * 4,    # o, float32
        layers * TINY["num_heads"] * t * 4,                     # lse
        picks, picks, picks, ROUTED * 4 * 4)))    # 4 held, a call a layer
    assert KEPT_NAMES[2:] == ROUTING_RESIDUAL_NAMES
    assert got == (want if remat != "none" else dict.fromkeys(KEPT_NAMES, 0))
