"""``ops/rows_to_tokens.py`` against a loop written out, interpreted (the
kernel compiled for a described v5e at the expert cells' widths is in
``test_flash_tpu_compile.py``; on the chip, ``chip_smoke.py``'s
``chunks`` fact)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.ops import rows_to_tokens as op


def written_out(rows, token, scale, live, tokens):
    y = np.zeros((tokens, rows.shape[1]), np.float32)
    for r in range(int(live)):
        y[token[r]] += np.float32(scale[r]) * rows[r]
    return y


def case(seed, count, d, tokens):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((count, d), np.float32),
            rng.integers(0, tokens, count).astype(np.int32),
            rng.random(count, np.float32))


@pytest.mark.parametrize("count,d,tokens", [
    (1024, 256, 64),    # two blocks of rows
    (288, 32, 96),      # blocks of 32 rows, a width that is no lane tile
    (15, 20, 5),        # one block that is no whole number of sublanes
    (512, 384, 40),     # one tile of three lane tiles
])
@pytest.mark.parametrize("share", [0.0, 0.4, 1.0])
def test_live_rows_are_added_to_their_tokens_in_order(count, d, tokens,
                                                      share):
    rows, token, scale = case(count + d, count, d, tokens)
    live = int(share * count)
    rows[live:] = np.nan            # unwritten: never read as a number
    got = op.rows_to_tokens(jnp.asarray(rows), jnp.asarray(token),
                            jnp.asarray(scale), jnp.int32(live), tokens)
    # to a rounding: compiled, a CPU fuses the product into the sum
    np.testing.assert_allclose(
        got, written_out(rows, token, scale, live, tokens), rtol=1e-6,
        atol=1e-6)
    assert np.all(np.asarray(got)[np.bincount(
        token[:live], minlength=tokens) == 0] == 0)


def test_tiles_of_the_width_follow_the_tokens(monkeypatch):
    """The widest whole-lane divisor of ``d`` whose block of all the tokens
    fits; several tiles give what one gives."""
    assert op._width(4096, 2560) == 1280 and op._width(4096, 2048) == 2048
    assert op._width(16384, 2048) == 512 and op._width(96, 32) == 32
    assert op._width(1 << 20, 256) == 128
    rows, token, scale = case(3, 256, 512, 32)
    args = (jnp.asarray(rows), jnp.asarray(token), jnp.asarray(scale),
            jnp.int32(200), 32)
    one = op.rows_to_tokens(*args)
    monkeypatch.setattr(op, "_RESIDENT", 32 * 128 * 4)
    assert op._width(32, 512) == 128
    np.testing.assert_array_equal(op.rows_to_tokens(*args), one)


def test_narrower_rows_are_added_up_in_float32():
    rows, token, scale = case(5, 64, 128, 4)
    got = op.rows_to_tokens(jnp.asarray(rows, jnp.bfloat16),
                            jnp.asarray(token), jnp.asarray(scale),
                            jnp.int32(64), 4)
    assert got.dtype == jnp.bfloat16
    want = written_out(np.asarray(jnp.asarray(rows, jnp.bfloat16),
                                  np.float32), token, scale, 64, 4)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=1e-2,
                               atol=1e-2)


@pytest.mark.parametrize("count,d,tokens", [(1024, 256, 64), (288, 32, 96)])
def test_rows_added_onto_a_carried_sum_equal_one_call_to_the_bit(
        count, d, tokens):
    """Two chunks of the rows, the second ``onto`` the first's result, are
    one call over all of them: a token's terms are added one after the
    other across the chunks (a routed layer's further chunks); the carried
    block halves the tile."""
    rows, token, scale = (jnp.asarray(a) for a in case(7, count, d, tokens))
    half, live = count // 2, count // 2 + count // 5
    with jax.disable_jit():     # operation by operation: no fused product
        whole = op.rows_to_tokens(rows, token, scale, jnp.int32(live),
                                  tokens)
        first = op.rows_to_tokens(rows[:half], token[:half], scale[:half],
                                  jnp.int32(half), tokens)
        both = op.rows_to_tokens(rows[half:], token[half:], scale[half:],
                                 jnp.int32(live - half), tokens, onto=first)
    np.testing.assert_array_equal(both, whole)
    np.testing.assert_allclose(
        whole, written_out(*(np.asarray(a) for a in (rows, token, scale)),
                           live, tokens), rtol=1e-6, atol=1e-6)
    # at the expert cells' 16,384 tokens: the first chunk's tile, a
    # further chunk's
    assert op._width(16384, 2560) == 512 and op._width(2 * 16384, 2560) == 256
