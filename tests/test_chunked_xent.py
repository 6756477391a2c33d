"""Chunked-vocab cross entropy vs the dense reference loss."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.models.base import cross_entropy_loss
from autodist_tpu.ops.chunked_xent import chunked_softmax_cross_entropy


def _data(n=24, e=16, v=512, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    h = jnp.asarray(rng.randn(n, e) * 0.5, dtype)
    w = jnp.asarray(rng.randn(v, e) * 0.5, dtype)
    y = jnp.asarray(rng.randint(0, v, (n,)), jnp.int32)
    return h, w, y


def _dense_loss(h, w, y):
    return cross_entropy_loss(jnp.einsum("ne,ve->nv", h, w), y)


@pytest.mark.parametrize("chunk", [64, 128, 512])
def test_forward_matches_dense(chunk):
    h, w, y = _data()
    dense = _dense_loss(h, w, y)
    chunked = chunked_softmax_cross_entropy(h, w, y, chunk=chunk)
    np.testing.assert_allclose(chunked, dense, rtol=1e-6)


def test_gradients_match_dense():
    h, w, y = _data()
    gd_h, gd_w = jax.grad(_dense_loss, argnums=(0, 1))(h, w, y)
    gc_h, gc_w = jax.grad(
        lambda h, w: chunked_softmax_cross_entropy(h, w, y, chunk=128),
        argnums=(0, 1))(h, w)
    np.testing.assert_allclose(gc_h, gd_h, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(gc_w, gd_w, rtol=1e-5, atol=1e-7)


def test_bf16_features_fp32_accumulation():
    h, w, y = _data(dtype=jnp.bfloat16)
    dense = _dense_loss(h.astype(jnp.float32), w.astype(jnp.float32), y)
    chunked = chunked_softmax_cross_entropy(h, w, y, chunk=128)
    np.testing.assert_allclose(float(chunked), float(dense), rtol=2e-2)
    g = jax.grad(lambda h, w: chunked_softmax_cross_entropy(
        h, w, y, chunk=128), argnums=(0, 1))(h, w)
    assert g[0].dtype == jnp.bfloat16 and g[1].dtype == jnp.bfloat16


def test_leading_shape_flattens():
    h, w, y = _data(n=24)
    hb = h.reshape(4, 6, -1)
    yb = y.reshape(4, 6)
    np.testing.assert_allclose(
        chunked_softmax_cross_entropy(hb, w, yb, chunk=128),
        chunked_softmax_cross_entropy(h, w, y, chunk=128), rtol=1e-7)


def test_indivisible_vocab_pads_and_masks():
    """V=500 with chunk=128 pads the table to 512; pad columns carry
    exactly zero probability and the result matches dense — including
    gradients (the pad rows of dW are sliced away by the pad's VJP)."""
    h, w, y = _data(v=500)
    np.testing.assert_allclose(
        chunked_softmax_cross_entropy(h, w, y, chunk=128),
        _dense_loss(h, w, y), rtol=1e-6)
    gd = jax.grad(_dense_loss, argnums=(0, 1))(h, w, y)
    gc = jax.grad(lambda h, w: chunked_softmax_cross_entropy(
        h, w, y, chunk=128), argnums=(0, 1))(h, w)
    np.testing.assert_allclose(gc[0], gd[0], rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(gc[1], gd[1], rtol=1e-5, atol=1e-7)
    assert gc[1].shape == w.shape


def test_lm1b_default_vocab_is_chunkable():
    """The lm1b default vocab (793472 = 2^7 * 6199) has no large
    power-of-two divisor; the op must handle it via padding, not demand
    divisibility (which only chunk<=128 could satisfy)."""
    h, w, y = _data(n=8, e=4, v=6199)   # 793472 = 128 * 6199
    assert (793472 % 8192) != 0         # the trap this guards
    loss = chunked_softmax_cross_entropy(h, w, y, chunk=512)
    np.testing.assert_allclose(loss, _dense_loss(h, w, y), rtol=1e-6)


def test_chunk_capped_at_vocab():
    h, w, y = _data(v=256)
    np.testing.assert_allclose(
        chunked_softmax_cross_entropy(h, w, y, chunk=8192),
        _dense_loss(h, w, y), rtol=1e-6)


def test_compiled_avoids_full_logits():
    """The point: peak temp memory must not contain an [N, V] logits
    buffer.  Compare compiled temp bytes for a vocab where dense logits
    would dominate (N=128, V=32768 -> 16.8 MB fp32 logits)."""
    h, w, y = _data(n=128, e=32, v=32768)

    dense = jax.jit(jax.grad(_dense_loss, argnums=(0, 1)))
    chunked = jax.jit(jax.grad(
        lambda h, w, y: chunked_softmax_cross_entropy(h, w, y, chunk=1024),
        argnums=(0, 1)))
    db = dense.lower(h, w, y).compile().memory_analysis().temp_size_in_bytes
    cb = chunked.lower(h, w, y).compile().memory_analysis().temp_size_in_bytes
    assert cb < db / 4, (cb, db)


@pytest.mark.parametrize("chunk", [64, 512])
def test_weights_match_the_plain_weighted_loss(chunk):
    """A weight a row (the block-diffusion objective's ``m / t``, zero for
    unmasked rows): value and both gradients against the plain weighted
    loss over ALL rows; the weights take no gradient."""
    h, w, y = _data()
    rng = np.random.RandomState(3)
    weights = jnp.asarray(
        np.where(rng.rand(24) < 0.5, 1.0 / rng.uniform(0.01, 1.0, 24), 0.0),
        jnp.float32)

    def plain(h, w, weights):
        logz = jax.nn.log_softmax(jnp.einsum("ne,ve->nv", h, w), axis=-1)
        return -jnp.sum(weights * logz[jnp.arange(24), y]) / 24

    def chunked(h, w, weights):
        return chunked_softmax_cross_entropy(h, w, y, chunk=chunk,
                                             weights=weights)

    np.testing.assert_allclose(chunked(h, w, weights),
                               plain(h, w, weights), rtol=1e-6)
    np.testing.assert_allclose(
        chunked(h, w, weights),
        cross_entropy_loss(jnp.einsum("ne,ve->nv", h, w), y, weights),
        rtol=1e-6)
    got = jax.grad(chunked, (0, 1, 2))(h, w, weights)
    want = jax.grad(plain, (0, 1))(h, w, weights)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-7)
    assert not np.asarray(got[2]).any()
    # shaped as the labels, whatever the leading shape
    np.testing.assert_allclose(
        chunked_softmax_cross_entropy(
            h.reshape(4, 6, -1), w, y.reshape(4, 6), chunk=chunk,
            weights=weights.reshape(4, 6)), plain(h, w, weights), rtol=1e-6)


def test_rows_of_weight_zero_add_nothing():
    """Zero-weight rows leave ``dW`` unchanged and take a zero ``dh``:
    the gradients with them are the gradients of the weighted rows alone
    (over the same count of rows)."""
    h, w, y = _data()
    weights = jnp.asarray([2.0, 0.0, 0.5] * 8, jnp.float32)
    kept = np.asarray(weights) > 0

    def loss(h, w, rows, weights):
        return chunked_softmax_cross_entropy(
            h[rows], w, y[rows], chunk=128, weights=weights[rows])

    every = np.arange(24)
    dh, dw = jax.grad(loss, (0, 1))(h, w, every, weights)
    dh_kept, dw_kept = jax.grad(loss, (0, 1))(h, w, every[kept], weights)
    assert not np.asarray(dh)[~kept].any()
    np.testing.assert_allclose(dw * 24, dw_kept * kept.sum(), rtol=1e-5,
                               atol=1e-8)
    np.testing.assert_allclose(dh[kept] * 24, dh_kept[kept] * kept.sum(),
                               rtol=1e-5, atol=1e-8)
    # other rows' values do not reach dW
    moved = h.at[1].set(h[1] * 3.0 + 1.0)
    np.testing.assert_array_equal(
        jax.grad(loss, 1)(moved, w, every, weights), dw)
