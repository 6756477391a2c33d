"""The ``k`` largest of every row as a MASK, exactly, without sorting.

``jax.lax.top_k`` gives indices, and for ``k`` in the thousands of rows
in the ten thousands it sorts every row.  What a masked kernel needs is
one bit an element, and that is a THRESHOLD: the ``k``-th largest value
of a row, found by fixing its 32 bits from the top, each round one
compare and one count over the row (a radix select), then ``x >= it``.
Ties at the threshold go to the lower positions, as ``top_k``'s do: a
second search, over the 14 or so bits of a position, entered only where
some row has more equals at its threshold than places left.

As XLA operations every round reads the whole ``[rows, N]`` block from main
memory.  The keye model (``models/gqa_dsa_moe_lm.py``) runs this form off a
TPU, through ``select_keys``; on a TPU the same search, round for round,
runs inside ``ops/index_select.py: dsa_select`` over a tile that stays in
VMEM, and this file is what the tests hold that kernel to.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

_BITS = 32


def ordered_bits(x: jax.Array) -> jax.Array:
    """float32 to uint32 so that ``a < b`` as numbers is ``a < b`` as
    unsigned integers (-0.0 below +0.0; NaNs at the ends by their sign)."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    negative = bits >> jnp.uint32(_BITS - 1) == 1
    return jnp.where(negative, ~bits, bits | jnp.uint32(1 << (_BITS - 1)))


def _highest_with(count_below_limit, bits: int, shape, dtype):
    """The largest ``p`` of ``bits`` bits, one a row, for which
    ``count_below_limit(p)`` holds, fixed bit by bit from the top; the
    predicate holds for 0 and is monotone."""
    def fix(i, p):
        cand = p | (jnp.ones((), dtype) << (bits - 1 - i).astype(dtype))
        return jnp.where(count_below_limit(cand), cand, p)

    return lax.fori_loop(0, bits, fix, jnp.zeros(shape, dtype))


def top_k_mask(x: jax.Array, k: int,
               valid: Optional[jax.Array] = None) -> jax.Array:
    """``[..., N]`` bool: the ``k`` largest of every row of ``x`` among
    the ``valid`` places (all of them where a row has ``k`` or fewer),
    a tie going to the lower position: the set ``jax.lax.top_k`` names."""
    n = x.shape[-1]
    u = ordered_bits(x)
    if valid is not None:
        u = jnp.where(valid, u, jnp.uint32(0))
    lead = x.shape[:-1] + (1,)

    def count(mask):
        return jnp.sum(mask, axis=-1, keepdims=True, dtype=jnp.int32)

    # the k-th largest: the highest value that k elements reach
    kth = _highest_with(lambda c: count(u >= c) >= k, _BITS, lead,
                        jnp.uint32)
    above, equal = u > kth, u == kth
    places = k - count(above)            # of the equals, this many are in
    position = lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)

    def lowest_equals(_):
        # the highest p with fewer than ``places`` equals before it
        p = _highest_with(
            lambda c: count(equal & (position < c)) < places,
            max(1, (n - 1).bit_length()), lead, jnp.int32)
        return equal & (position <= p)

    crowded = count(equal) > places
    if valid is not None:       # a row with k or fewer valid takes them all
        crowded &= count(valid) > k
    picked = above | lax.cond(jnp.any(crowded), lowest_equals,
                              lambda _: equal, None)
    if valid is None:
        return picked
    return jnp.where(count(valid) > k, picked & valid, valid)
