"""``ops/gated_delta_rule.py``: the chunked form and its two kernels (under
the Pallas interpreter) against the recurrence token by token, at
``highest``: the forward and every input's gradient, chunks of 16 and 64,
segments of one chunk and of all, value heads sharing key heads, and decays
down to -20 a token with nothing but finite numbers anywhere.  The kernels
form what no state enters in VMEM (chunks two by two, a pair's blocks side
by side on the lanes, ``T`` by doublings of the whole block) and the
backward's is written out whole (the scan's transpose, ``T``'s
cotangent by the inverse's own rule, the elementwise lines by hand): held to
the recurrence's gradient and to ``jax.vjp`` of the plain form, the
doublings to ``_inverse``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.ops import gated_delta_rule as gdr
from autodist_tpu.ops.gated_delta_rule import gated_delta_rule, recurrence


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def operands(seed=0, b=2, t=128, hk=2, share=2, d=32, rate=1.0):
    """``q, k`` L2-normed (``q`` scaled), ``g = -rate * softplus(.)``."""
    ks = jax.random.split(jax.random.key(seed), 5)
    q, k = (jax.random.normal(key, (b, t, hk, d)) for key in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, hk * share, d))
    g = -rate * jax.nn.softplus(
        jax.random.normal(ks[3], (b, t, hk * share)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, hk * share)))
    return q, k, v, g, beta


def value_and_grads(fn, args, seed=9):
    ct = jax.random.normal(jax.random.key(seed), args[2].shape)
    return jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * ct),
                              argnums=tuple(range(5)))(*args)


def gaps(got, want):
    return [float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))
            for a, b in zip(got, want)]


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
@pytest.mark.parametrize("chunk,segment", [(16, 32), (16, 2), (64, 1)])
@pytest.mark.parametrize("rate", [1.0, 0.05])
def test_chunked_form_is_the_recurrence(kernel, chunk, segment, rate):
    args = operands(rate=rate)
    want = recurrence(*args)
    got = gated_delta_rule(*args, chunk=chunk, segment=segment,
                           interpret=kernel or None)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    _, g_want = value_and_grads(recurrence, args)
    _, g_got = value_and_grads(
        lambda *a: gated_delta_rule(*a, chunk=chunk, segment=segment,
                                    interpret=kernel or None), args)
    assert max(gaps(g_got, g_want)) < 2e-5


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
@pytest.mark.parametrize("chunk", [16, 64])
def test_decays_of_minus_twenty_a_token_stay_finite(kernel, chunk):
    """``A = 16``: ``g`` reaches -21 a token, -1,300 a chunk of 64, where
    ``exp(-G)`` is ``inf``: forward and every gradient finite, and the
    recurrence's."""
    args = operands(seed=3, rate=16.0)
    assert float(args[3].min()) < -20.0

    def fn(*a):
        return gated_delta_rule(*a, chunk=chunk, segment=2,
                                interpret=kernel or None)

    got, grads = value_and_grads(fn, args)
    want, g_want = value_and_grads(recurrence, args)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in (got,) + grads)
    assert abs(float(got - want)) < 1e-5 * (1 + abs(float(want)))
    assert max(gaps(grads, g_want)) < 5e-5


def plain_and_written_out(args, chunk, segment):
    """Value and the five gradients of the plain form (``jax.vjp`` of it a
    segment at a time) and of the kernels', written out."""
    return [value_and_grads(lambda *a: gated_delta_rule(
        *a, chunk=chunk, segment=segment, interpret=interpret), args)
        for interpret in (None, True)]


@pytest.mark.parametrize("chunk,segment", [(16, 32), (16, 2), (64, 1)],
                         ids=["whole", "pairs", "single"])
@pytest.mark.parametrize("rate", [1.0, 0.05, 16.0])
def test_written_out_backward_is_the_plain_forms_transpose(chunk, segment,
                                                           rate):
    """Two value heads a key head (``operands``' default), a segment that
    is the whole sequence, one of two chunks and one of a single chunk,
    ``g`` from -0.1 to under -20 a token (``rate`` 16): every cotangent of
    the backward kernel against ``jax.vjp`` of the plain form, with which
    it shares not a line, and against the recurrence's, every number
    finite."""
    args = operands(seed=1, rate=rate)
    assert (float(args[3].min()) < -20.0) == (rate == 16.0)
    (want, g_want), (got, g_got) = plain_and_written_out(args, chunk, segment)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in (got,) + g_got)
    assert abs(float(got - want)) < 1e-5 * (1 + abs(float(want)))
    assert max(gaps(g_got, g_want)) < 1e-5
    _, g_token = value_and_grads(recurrence, args)
    assert max(gaps(g_got, g_token)) < 5e-5


@pytest.mark.parametrize("segment", [1, 2, 4])
def test_states_cotangent_is_carried_across_segments(segment):
    """The gradient does not change with ``segment``: cut into eight, four
    or two segments, ``dS`` handed from each to the one before it, the
    written-out backward gives what it gives over the whole sequence (one
    segment, which carries nothing)."""
    args = operands(seed=7)
    whole, cut = (value_and_grads(lambda *a: gated_delta_rule(
        *a, chunk=16, segment=s, interpret=True), args)[1]
        for s in (8, segment))
    assert max(gaps(cut, whole)) < 2e-6


@pytest.mark.parametrize("c", [2, 4, 16, 64, 128])
def test_inverse_by_doublings_is_the_inverse_by_blocks(c):
    """What the kernels form ``T`` by (doublings of the whole block, a turn
    ONE stacked product; two pairs of blocks, a pair's side by side on the
    lanes) against ``_inverse`` (16-wide blocks, joined): to 1e-6 of the
    largest entry, and nothing above the diagonals."""
    a = jnp.tril(jax.random.normal(jax.random.key(4), (4, c, c)) * 0.2, -1)
    packed = gdr._inverse_doubling(jnp.concatenate([a[0::2], a[1::2]], -1))
    got = jnp.stack([packed[..., :c], packed[..., c:]], 1).reshape(a.shape)
    assert gaps([got], [gdr._inverse(a)])[0] < 1e-6
    assert not np.triu(np.asarray(got), 1).any()
    np.testing.assert_allclose(got @ (jnp.eye(c) + a), jnp.broadcast_to(
        jnp.eye(c), a.shape), atol=1e-5)


def test_a_pair_of_chunks_side_by_side():
    """``G`` and ``beta`` reach a kernel a pair of chunks a ROW, the two
    side by side: the columns over the pair's tokens, each chunk's last
    entry and the packed decays made of them, masked before the ``exp``
    (a row of -1,300 beside a mild one and nothing but finite numbers); a
    column laid back as a row, the diagonal blocks of a pair's product
    packed and laid back."""
    steep = jnp.cumsum(-20.0 * jnp.ones(64) - jnp.arange(64.0) / 64)
    g = jnp.stack([steep, steep / 400])                        # [2, 64]
    beta = jnp.stack([jnp.linspace(0.1, 0.9, 64), jnp.linspace(0.9, 0.2, 64)])
    d = gdr._vectors(g.reshape(1, 1, 128), beta.reshape(1, 1, 128))
    np.testing.assert_array_equal(d.beta[0, :, 0], beta.reshape(128))
    np.testing.assert_allclose(d.e_g[0, :, 0], jnp.exp(g).reshape(128),
                               rtol=1e-6)
    np.testing.assert_array_equal(gdr._row(d.beta), beta.reshape(1, 1, 128))
    np.testing.assert_array_equal(gdr._column(gdr._row(d.e_d)), d.e_d)
    assert [x.shape for x in d.gc] == [(1, 1, 1)] * 2
    assert float(d.gc[0][0, 0, 0]) == float(jnp.exp(steep[-1])) == 0.0
    assert float(d.gc[1][0, 0, 0]) == float(jnp.exp(steep[-1] / 400)) > 0.0
    want = jnp.tril(jnp.exp(jnp.tril(g[:, :, None] - g[:, None, :])))
    np.testing.assert_allclose(d.decay[0], jnp.concatenate(list(want), 1),
                               rtol=1e-6)
    assert bool(jnp.all(jnp.isfinite(d.decay)))
    np.testing.assert_allclose(
        d.e_d[0, :, 0], jnp.exp(g[:, -1:] - g).reshape(128), rtol=1e-6)
    np.testing.assert_array_equal(d.beta_p[0, :, :64], jnp.broadcast_to(
        beta[0][:, None], (64, 64)))
    np.testing.assert_array_equal(d.beta_p[0, :, 64:], jnp.broadcast_to(
        beta[1][:, None], (64, 64)))
    assert np.asarray(d.strict).sum() == 2 * 64 * 63 // 2
    whole = jax.random.normal(jax.random.key(0), (3, 128, 128))
    packed = gdr._pack(whole)
    np.testing.assert_array_equal(packed[..., :64], whole[:, :64, :64])
    np.testing.assert_array_equal(packed[..., 64:], whole[:, 64:, 64:])
    blocks = gdr._blocks(packed)
    np.testing.assert_array_equal(gdr._pack(blocks), packed)
    assert not np.asarray(blocks[:, :64, 64:]).any()
    assert not np.asarray(blocks[:, 64:, :64]).any()
    np.testing.assert_allclose(
        gdr._halves(packed)[:, 64:, 0], whole[:, 64:, 64:].sum(-1), rtol=1e-5)


def test_sixteen_key_heads_each_serve_two_value_heads():
    """Value heads ``2j, 2j + 1`` read key head ``j``: the same numbers as
    with every key head repeated."""
    args = operands(seed=5, b=1, t=64, hk=16, share=2, d=16)
    q, k, v, g, beta = args
    shared = gated_delta_rule(*args, chunk=16)
    repeated = gated_delta_rule(jnp.repeat(q, 2, axis=2),
                                jnp.repeat(k, 2, axis=2), v, g, beta,
                                chunk=16)
    np.testing.assert_allclose(shared, repeated, atol=1e-6)
    np.testing.assert_allclose(shared, recurrence(*args), atol=2e-6)
    # and head 5's output moves with key head 2 alone
    moved = gated_delta_rule(q, k.at[:, :, 2].multiply(-1.0), v, g, beta,
                             chunk=16)
    changed = np.abs(np.asarray(moved - shared)).max(axis=(0, 1, 3)) > 1e-6
    assert changed.tolist() == [h in (4, 5) for h in range(32)]


@pytest.mark.parametrize("c", [8, 16, 64, 128])
def test_inverse_by_blocks_and_doublings(c):
    a = jnp.tril(jax.random.normal(jax.random.key(0), (3, c, c)) * 0.2, -1)
    inv = gdr._inverse(a)
    np.testing.assert_allclose(
        jnp.matmul(inv, jnp.eye(c) + a), jnp.broadcast_to(
            jnp.eye(c), a.shape), atol=1e-4)
    assert not np.triu(np.asarray(inv), 1).any()
    with pytest.raises(ValueError, match="power of two"):
        gdr._inverse(jnp.zeros((48, 48)))


def test_the_backward_is_handed_what_the_forward_kept():
    """Under a checkpoint that keeps ``RESIDUAL_NAMES`` the differentiated
    program holds the forward kernel ONCE beside the backward's (both 4
    programs by 2 segments of 2 chunks): the backward neither runs the
    forward's again nor asks for its output; without the names it runs it
    a second time."""
    import re

    args = operands(b=1, t=64, d=16)
    keep = jax.checkpoint_policies.save_only_these_names(
        *gdr.RESIDUAL_NAMES)

    def kernels(**policy):
        def loss(*a):
            return jnp.sum(jax.checkpoint(lambda *b: gated_delta_rule(
                *b, chunk=16, segment=2, interpret=True) ** 2, **policy)(*a))

        text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(
            *args))
        assert text.count("GridMapping(grid=(4, 2)") \
            == text.count("pallas_call[")
        return sorted(re.findall(r"name=(gdn_scan\w*)", text))

    assert kernels(policy=keep) == [gdr.KERNEL_NAME, gdr.BWD_KERNEL_NAME]
    assert kernels() == [gdr.KERNEL_NAME] * 2 + [gdr.BWD_KERNEL_NAME]


def test_shapes_it_refuses():
    q, k, v, g, beta = operands(t=48)
    with pytest.raises(ValueError, match="chunks of 64"):
        gated_delta_rule(q, k, v, g, beta, chunk=64)
    with pytest.raises(ValueError, match="value heads"):
        gated_delta_rule(q, k, v[:, :, :3], g[..., :3], beta[..., :3],
                         chunk=16)


def test_flops_per_token_by_hand():
    f = gdr.flops_per_token(128, 128, 64)
    assert f["recurrence"] == 7 * 128 * 128 == 114_688
    # T by doublings of the whole 64 x 64 block: P^2, four turns of the
    # stacked pair (two products each) and the last, ten products of
    # 2 * 64^3: 81,920 a token
    assert gdr._doubling_flops(64) == 10 * 524_288
    assert [gdr._doubling_flops(c) for c in (1, 2, 4, 16)] == [
        0, 0, 2 * 128, 6 * 8_192]
    # K K^T and Q K^T (a VALUE head forms its own), T, W and U, (Q exp(G))
    # S and W S, ((Q K^T) * D) V', (K exp(G_C - G))^T V'
    assert f["computed"] == (32_768 + 81_920 + 32_768 + 65_536 + 16_384
                             + 32_768) == 262_144
