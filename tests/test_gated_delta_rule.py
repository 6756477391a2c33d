"""``ops/gated_delta_rule.py``: the chunked form and its scan kernel (under
the Pallas interpreter) against the recurrence token by token, at
``highest``: the forward and every input's gradient, chunks of 16 and 64,
backward segments of one chunk and of all, value heads sharing key heads,
and decays down to -20 a token with nothing but finite numbers anywhere.
The kernel's backward is written out (its scan a second kernel, ``T``'s
cotangent by the inverse's own rule): held to the recurrence's gradient, to
``jax.vjp`` of the plain form, and piece by piece to ``jax.vjp`` of
``_scan_plain`` and of ``_inverse``.
"""
import math

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
    segment at a time) and of the kernel's, written out."""
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
    the written-out backward against ``jax.vjp`` of the plain form, with
    which it shares ``_prepare`` and nothing of the backward, and against
    the recurrence's, every number finite."""
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


@pytest.mark.parametrize("c,dk,dv", [(16, 32, 32), (64, 16, 128)])
def test_scan_backward_kernel_is_the_plain_scans_transpose(c, dk, dv):
    """One segment of five chunks entered with a state and left with a
    state's cotangent: all seven cotangents against ``jax.vjp`` of
    ``_scan_plain``."""
    p, n = 3, 5
    ks = jax.random.split(jax.random.key(2), 9)
    shapes = [(p, n, c, dk), (p, n, c, dk), (p, n, c, dv), (p, n, c, c),
              (p, n, c, dk), (p, n), (p, dk, dv)]
    *xs, s0 = (0.3 * jax.random.normal(key, shape)
               for key, shape in zip(ks, shapes))
    xs[5] = jax.nn.sigmoid(xs[5])                        # gc in (0, 1)
    do = jax.random.normal(ks[7], (p, n, c, dv))
    dsn = jax.random.normal(ks[8], (p, dk, dv))
    _, pull = jax.vjp(gdr._scan_plain, *xs, s0)
    want = pull((do, dsn))
    cotangents, ds0 = gdr._scan_bwd_pallas(*xs, s0, do, dsn, True)
    assert max(gaps(cotangents + (ds0,), want)) < 1e-5


@pytest.mark.parametrize("c", [16, 64])
def test_inverse_by_rule_is_the_inverses_transpose(c):
    """``dA = -(T^T dT T^T)`` against autodiff through the doublings and
    the joins, on the strictly lower triangle (all of ``A`` there is)."""
    ks = jax.random.split(jax.random.key(6))
    a = jnp.tril(jax.random.normal(ks[0], (3, c, c)) * 0.2, -1)
    dt = jax.random.normal(ks[1], (3, c, c))
    (want,), (got,) = (jax.vjp(f, a)[1](dt)
                       for f in (gdr._inverse, gdr._inverse_by_rule))
    assert gaps([jnp.tril(got, -1)], [jnp.tril(want, -1)])[0] < 2e-6


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
    program holds the forward's scan kernel ONCE (grid: 4 programs by 4
    chunks) beside the backward's (4 by twice the segment's turns): the
    backward neither runs the forward's again nor asks for its output."""
    args = operands(b=1, t=64, d=16)
    keep = jax.checkpoint_policies.save_only_these_names(
        *gdr.RESIDUAL_NAMES)

    def loss(*a):
        return jnp.sum(jax.checkpoint(
            lambda *b: gated_delta_rule(*b, chunk=16, interpret=True) ** 2,
            policy=keep)(*a))

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args))
    turns = 2 * 4 // math.gcd(4, gdr._CHUNKS_A_TURN)
    forward, backward = ("GridMapping(grid=(4, 4)",
                         f"GridMapping(grid=(4, {turns})")
    assert (text.count("pallas_call["), text.count(forward),
            text.count(backward)) == (2, 1, 1)
    free = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(jax.checkpoint(
        lambda *b: gated_delta_rule(*b, chunk=16, interpret=True) ** 2)(*a)),
        argnums=(0, 1, 2, 3, 4)))(*args))
    assert (free.count("pallas_call["), free.count(forward),
            free.count(backward)) == (3, 2, 1)


def test_shapes_it_refuses():
    q, k, v, g, beta = operands(t=48)
    with pytest.raises(ValueError, match="chunks of 64"):
        gated_delta_rule(q, k, v, g, beta, chunk=64)
    with pytest.raises(ValueError, match="value heads"):
        gated_delta_rule(q, k, v[:, :, :3], g[..., :3], beta[..., :3],
                         chunk=16)


def test_flops_per_token_by_hand():
    f = gdr.flops_per_token(128, 128, 64, 2)
    assert f["recurrence"] == 7 * 128 * 128 == 114_688
    # T: four 16-wide blocks by seven products each, joined by 2 x 2 of 16
    # and 2 of 32 wide: 393,216 a chunk of 64 tokens
    assert gdr._inverse_flops(16) == 7 * 2 * 16 ** 3
    assert gdr._inverse_flops(64) == 4 * 57_344 + 2 * 16_384 + 131_072 \
        == 393_216
    # K K^T and Q K^T shared by two heads, T, W and U, (Q exp(G)) S and
    # W S, ((Q K^T) * D) V', (K exp(G_C - G))^T V'
    assert f["computed"] == (16_384 + 6_144 + 32_768 + 65_536 + 16_384
                             + 32_768) == 169_984
