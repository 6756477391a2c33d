"""The plain reference against ``transformer_lm`` at a tiny size: logits,
loss and gradients, and AdamW against optax."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import weights
from benchmark.reference import gpt2

from autodist_tpu.models.transformer_lm import transformer_lm


@pytest.fixture(scope="module")
def tiny():
    spec = transformer_lm(vocab_size=257, num_layers=2, num_heads=2,
                          head_dim=32, d_ff=256, max_len=64,
                          dtype=jnp.float32)
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    params = weights.make_weights(shapes, 2**31 + 7)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 257, (4, 64)), jnp.int32)
    return spec, params, tokens


def test_weights_are_seeded_and_typed(tiny):
    spec, params, _ = tiny
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    again = weights.make_weights(shapes, 2**31 + 7)
    other = weights.make_weights(shapes, 2**31 + 8)
    same = jax.tree_util.tree_map(lambda a, b: bool((a == b).all()),
                                  params, again)
    assert all(jax.tree_util.tree_leaves(same))
    assert not bool((params["embed"] == other["embed"]).all())
    assert float(params["decoder"]["ln_final"]["scale"].min()) == 1.0
    assert float(jnp.std(params["embed"])) == pytest.approx(0.02, rel=0.05)


def test_logits_match_the_program(tiny):
    spec, params, tokens = tiny
    got = gpt2.logits_fn(gpt2.to_reference(params), tokens)
    want = spec.apply_fn(params, tokens)
    assert float(jnp.abs(got - want).max()) < 2e-5


def test_loss_and_gradients_match_the_program(tiny):
    spec, params, tokens = tiny
    loss, grads = gpt2.loss_and_grads(gpt2.to_reference(params), tokens,
                                      row_block=2)
    want_loss, want = jax.value_and_grad(spec.loss_fn)(
        params, {"tokens": tokens})
    assert float(loss) == pytest.approx(float(want_loss), abs=1e-5)
    got_n = gpt2.flatten(gpt2.leaf_norms(grads))
    want_n = gpt2.flatten(gpt2.leaf_norms(want))
    assert set(got_n) == set(want_n)
    for k in want_n:
        assert got_n[k] == pytest.approx(want_n[k], rel=1e-3, abs=1e-7), k


def test_bfloat16_products_round_both_operands_both_ways():
    """``bfloat16_products``: float32 in and out, both operands of the
    product on the bfloat16 grid, and in the backward pass the cotangent
    too (it is an operand there); nothing else is rounded."""
    rng = np.random.default_rng(3)
    a, w, g = (jnp.asarray(rng.normal(size=s), jnp.float32)
               for s in ((8, 16), (16, 4), (8, 4)))
    r = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)   # noqa: E731
    with jax.default_matmul_precision("highest"):
        y, vjp = jax.vjp(
            lambda a, w: gpt2._mm("ij,jk->ik", a, w, "bfloat16"), a, w)
        da, dw = vjp(g)
        np.testing.assert_allclose(y, r(a) @ r(w), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(da, r(g) @ r(w).T, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(dw, r(a).T @ r(g), rtol=1e-5, atol=1e-6)
        assert float(jnp.abs(y - a @ w).max()) > 1e-4     # and it rounds


def test_precisions_order_as_named(tiny):
    """Against float32, the stated precision of the chip's cells
    (bfloat16 products) errs, bfloat16 storage errs more, int8 most."""
    _, params, tokens = tiny
    ref = gpt2.to_reference(params)
    with jax.default_matmul_precision("highest"):
        want = gpt2.logits_fn(ref, tokens)
        err = {c: float(jnp.abs(gpt2.logits_fn(ref, tokens, c) - want).max())
               for c in ("bfloat16_products", "bfloat16", "int8")}
    assert 0 < err["bfloat16_products"] < err["bfloat16"] < err["int8"]


def test_adamw_is_optax_adamw(tiny):
    spec, params, tokens = tiny
    opt = optax.adamw(1e-3)
    state = opt.init(params)
    p = params
    batches = [tokens, tokens[::-1], tokens[:, ::-1]]
    for b in batches:
        g = jax.grad(spec.loss_fn)(p, {"tokens": b})
        upd, state = opt.update(g, state, p)
        p = optax.apply_updates(p, upd)
    losses, first, delta, _ = gpt2.train_steps(
        gpt2.to_reference(params), batches, row_block=2)
    want = gpt2.flatten(gpt2.leaf_diff_norms(p, params))
    assert len(losses) == 3 and all(np.isfinite(losses))
    for k in want:
        assert delta[k] == pytest.approx(want[k], rel=2e-3), k
    g0 = gpt2.flatten(gpt2.leaf_norms(
        jax.grad(spec.loss_fn)(params, {"tokens": tokens})))
    for k in g0:
        assert first[k] == pytest.approx(g0[k], rel=1e-3, abs=1e-7), k


def test_served_gap_is_zero_for_the_references_own_choice(tiny):
    _, params, tokens = tiny
    ref = gpt2.to_reference(params)
    seq = tokens[0]
    chosen = gpt2.first_choices(ref, seq)
    assert float(gpt2.gaps_below_best(ref, seq, chosen).max()) == 0.0
    wrong = (chosen + 1) % 257
    assert float(gpt2.gaps_below_best(ref, seq, wrong).min()) > 0.0
