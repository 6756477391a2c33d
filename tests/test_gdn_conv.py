"""``ops/gdn_conv.py``'s kernel pair against the plain lines
(``models/gdn_moe_lm.py: conv_qkvz``: ``causal_conv``, silu, ``l2norm``, the
split), under the Pallas interpreter on a CPU at shrunk sizes.

A CPU multiplies and adds float32 as the plain lines do, so the two differ
by the order of a tap's four terms and of a norm's sum: the outputs are
held to 1e-6 of the plain form's largest, the cotangent of ``qkvz`` and the
three taps' gradients to 1e-5 of ``jax.vjp`` of the plain form.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.models import gdn_moe_lm as model
from autodist_tpu.ops import gdn_conv
from autodist_tpu.ops.gdn_conv import conv_silu_l2norm

import _routed_cases as routed_cases

T, DL, TAPS = 24, 8, 4


def operands(b, hk, share, t=T, seed=0):
    """``(qkvz, (w_q, w_k, w_v), the four outputs' cotangents)``."""
    rng = np.random.RandomState(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    qkvz = normal(b, t, hk, 2 * DL * (1 + share))
    taps = tuple(normal(hk, n, TAPS) * 0.5 for n in (DL, DL, share * DL))
    cotangents = tuple(normal(b, t, heads, DL)
                       for heads in (hk, hk, share * hk, share * hk))
    return qkvz, taps, cotangents


def plain(qkvz, *taps):
    return model.conv_qkvz(qkvz, *taps, DL ** -0.5)


def kernel(qkvz, *taps, tile):
    return conv_silu_l2norm(qkvz, *taps, DL ** -0.5, tile=tile,
                            interpret=True)


def with_gradients(fn, qkvz, taps, cotangents):
    """``(the four outputs, the cotangents of qkvz and the three taps)``,
    in one compiled program."""
    def both(qkvz, taps, cotangents):
        out, pull = jax.vjp(fn, qkvz, *taps)
        return out, pull(cotangents)

    return jax.jit(both)(qkvz, taps, cotangents)


def assert_match(got, want):
    """Outputs to 1e-6 and gradients to 1e-5 of the plain form's largest."""
    for side, rtol in zip((0, 1), (1e-6, 1e-5)):
        assert len(got[side]) == len(want[side]) == 4
        for y, y_want in zip(got[side], want[side]):
            assert_close(y, y_want, rtol)


def assert_close(got, want, rtol):
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=rtol * float(jnp.max(jnp.abs(want))))


# tiles of 8 fill 24 tokens; of 16 leave the second half empty; 18 tokens
# leave the third tile of 8 two, fewer than the taps reach back; a tile of
# 64 is cut to the sequence
@pytest.mark.parametrize("t,tile", [(24, 8), (24, 16), (18, 8), (24, 64)])
@pytest.mark.parametrize("share", [1, 2])
@pytest.mark.parametrize("hk,b", [(1, 1), (1, 2), (3, 1)])
def test_outputs_and_gradients_match_the_plain_lines(hk, b, share, t, tile):
    qkvz, taps, cotangents = operands(b, hk, share, t=t,
                                      seed=hk + share + tile)
    got = with_gradients(functools.partial(kernel, tile=tile), qkvz, taps,
                         cotangents)
    assert_match(got, with_gradients(plain, qkvz, taps, cotangents))
    np.testing.assert_array_equal(got[0][3], qkvz[..., -share * DL:].reshape(
        got[0][3].shape))                                   # z as it is


def test_three_value_heads_a_key_head_in_whole_tiles_of_the_default():
    """No ``tile`` handed: the module's, cut to the sequence."""
    qkvz, taps, cotangents = operands(1, 2, 3, t=16)
    assert_match(
        with_gradients(lambda x, *w: conv_silu_l2norm(
            x, *w, DL ** -0.5, interpret=True), qkvz, taps, cotangents),
        with_gradients(plain, qkvz, taps, cotangents))


@pytest.mark.parametrize("tile", [8, 16])
def test_no_later_token_is_read(tile):
    """What ``test_gdn_moe_lm.py`` asks of ``causal_conv``, of the op: with
    the tokens from 13 on zeroed, the outputs before 13 are the same to
    the bit (13 lies inside a tile of either length), and so is their
    cotangent's part that earlier outputs alone feed."""
    qkvz, taps, cotangents = operands(2, 2, 2)
    f = functools.partial(kernel, tile=tile)
    got = f(qkvz, *taps)
    cut = f(qkvz.at[:, 13:].set(0.0), *taps)
    for y, y_cut in zip(got, cut):
        np.testing.assert_array_equal(y[:, :13], y_cut[:, :13])
    # cotangents that end at token 13 reach no later token of qkvz
    early = tuple(c.at[:, 13:].set(0.0) for c in cotangents)
    dx = jax.vjp(f, qkvz, *taps)[1](early)[0]
    assert not np.asarray(dx[:, 13:]).any()
    assert np.asarray(dx[:, 12]).any()


@pytest.mark.parametrize("tile", [8, 16])
def test_the_halo_at_a_tile_boundary_and_at_the_first_token(tile):
    """Token 0 sees zeros before it (its pre-activation is its own column
    times the last tap), and the first token of the second tile sees the
    first tile's last three: written out by hand from the definition."""
    qkvz, taps, _ = operands(1, 2, 2, seed=5)
    q, k, v, _ = kernel(qkvz, *taps, tile=tile)
    w = jnp.concatenate(taps, axis=1)                        # [Hk, Cc, K]
    x = qkvz[0, :, :, :w.shape[1]]                           # [T, Hk, Cc]

    def by_hand(t):
        pre = sum(w[..., TAPS - 1 - i] * x[t - i]
                  for i in range(min(TAPS, t + 1)))
        act = pre * jax.nn.sigmoid(pre)
        qh, kh = (a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
                  for a in (act[:, :DL], act[:, DL:2 * DL]))
        return qh * DL ** -0.5, kh, act[:, 2 * DL:].reshape(-1, DL)

    for t in (0, 1, tile - 1, tile, tile + 2, T - 1):
        for got, want in zip((q[0, t], k[0, t], v[0, t]), by_hand(t)):
            np.testing.assert_allclose(got, want, atol=2e-6)
    # and nothing before the sequence leaks in from the sequence before it
    two = jnp.concatenate([qkvz + 1.0, qkvz])
    again = kernel(two, *taps, tile=tile)
    for y, y_two in zip((q, k, v), again):
        np.testing.assert_array_equal(y[0], y_two[1])


def test_the_taps_gradient_sums_over_sequences():
    qkvz, taps, cotangents = operands(2, 2, 2, seed=7)
    f = functools.partial(kernel, tile=8)
    both = jax.vjp(f, qkvz, *taps)[1](cotangents)[1:]
    one_by_one = [jax.vjp(f, qkvz[i:i + 1], *taps)[1](
        tuple(c[i:i + 1] for c in cotangents))[1:] for i in range(2)]
    for g, g0, g1 in zip(both, *one_by_one):
        assert_close(g, g0 + g1, 1e-6)


@pytest.mark.parametrize("what", ["tile", "taps", "heads", "columns"])
def test_shapes_that_do_not_fit_are_refused(what):
    qkvz, (w_q, w_k, w_v), _ = operands(1, 2, 2)
    tile = 12 if what == "tile" else 8          # whole sublanes of tokens
    if what == "taps":
        w_k = w_k[:, :, :3]
    elif what == "heads":
        w_v = w_v[:1]
    elif what == "columns":
        qkvz = qkvz[..., :-DL]
    with pytest.raises(ValueError, match="qkvz"):
        conv_silu_l2norm(qkvz, w_q, w_k, w_v, 1.0, tile=tile, interpret=True)


def test_bfloat16_operands_come_back_in_their_type():
    qkvz, taps, cotangents = operands(1, 2, 2)
    narrow = [a.astype(jnp.bfloat16) for a in (qkvz, *taps)]
    got, pull = jax.vjp(functools.partial(kernel, tile=8), *narrow)
    want = plain(*(a.astype(jnp.float32) for a in narrow))
    for y, y_want in zip(got, want):
        assert y.dtype == jnp.bfloat16
        assert_close(y.astype(jnp.float32), y_want, 1e-2)
    assert {g.dtype for g in pull(tuple(
        c.astype(jnp.bfloat16) for c in cotangents))} == {
            jnp.dtype(jnp.bfloat16)}


def test_each_kernel_kind_is_traced_once_a_shape():
    """Issue 48 (PR 44's lesson): a layer's call of the op does not lower
    its kernel anew."""
    qkvz, taps, cotangents = operands(1, 2, 2, seed=11)
    f = functools.partial(kernel, tile=16)
    jax.vjp(f, qkvz, *taps)[1](cotangents)
    sizes = gdn_conv._forward._cache_size(), gdn_conv._backward._cache_size()
    for seed in (12, 13):
        x, w, c = operands(1, 2, 2, seed=seed)
        jax.vjp(f, x, *w)[1](c)
    assert sizes == (gdn_conv._forward._cache_size(),
                     gdn_conv._backward._cache_size())


TINY = dict(vocab_size=61, num_layers=2, d_model=32, full_interval=2,
            linear_key_heads=2, linear_value_heads=4, linear_head_dim=8,
            conv_kernel=4, num_heads=4, num_kv_heads=2, head_dim=16,
            rotary_dim=4, d_expert=12, d_shared=12, num_experts=16, top_k=3,
            seq_len=64, chunk=16, block_k=32, moe_slice=64)


def test_the_model_with_the_op_is_the_model_with_the_plain_lines(monkeypatch):
    """``gdn_moe_lm``'s loss and every gradient with the kernels (as a TPU
    takes them, here under the interpreter, tiles of 16 in sequences of
    64; the layers' checkpoints run the forward's a second time) equal
    those with the plain lines a CPU takes."""
    def loss_and_grads():
        spec = model.gdn_moe_lm(**TINY, remat="full")
        params = routed_cases.seeded(
            jax.eval_shape(spec.init, jax.random.key(0)), 3, gain=8.0)
        batch = {"tokens": routed_cases.tokens(5, t=64)}
        text = str(jax.make_jaxpr(jax.grad(spec.loss_fn))(params, batch))
        return jax.jit(jax.value_and_grad(spec.loss_fn))(params, batch), text

    (want, want_grads), text = loss_and_grads()
    assert "gdn_conv" not in text
    monkeypatch.setattr(gdn_conv, "_use_interpret", lambda: False)
    monkeypatch.setattr(gdn_conv, "conv_silu_l2norm", functools.partial(
        conv_silu_l2norm, tile=16, interpret=True))
    (got, grads), text = loss_and_grads()
    assert "gdn_conv_bwd" in text
    np.testing.assert_allclose(got, want, rtol=1e-6)
    routed_cases.assert_every_gradient_matches(
        grads, want_grads, 1e-5, lambda name: False)
