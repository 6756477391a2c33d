"""A sequence's index scores, the ``topk`` largest of every row and their
packed words as ONE kernel that keeps a tile of queries' scores in VMEM.

What ``models/gqa_dsa_moe_lm.py: select_keys`` computes as XLA operations
(``index_scores``, ``ops/topk_select.py: top_k_mask``, ``ops/flash_
attention.py: pack_selection``) a block of 1,024 queries at a time, every
block's ``[rows, keys]`` float32 scores crossing main memory once a head,
once a round of the threshold search and once for the words.  Here the
grid runs over tiles of ``bq`` queries and a tile's scores never leave
VMEM; only the words do.  For a tile that ends past row ``topk``:

1. **Scores**, keys DOWN and queries ALONG (the words' orientation): for
   the key blocks ``0 .. the tile's last row`` and the heads in their
   order, ``acc += w[:, j] * relu(kI . qI[:, j])`` in float32.  The
   product is float32 at ``highest``, which on a TPU is six products of
   the operands' bfloat16 terms (``x = hi + mid + lo``: hi.hi, mid.hi,
   hi.mid, hi.lo, lo.hi, mid.mid).  The contraction is 64 deep on an
   array 128 deep, so the SAME six are formed two to a pass, the terms
   stacked along the contraction (:func:`stacked_terms`), and the three
   passes are one product 384 deep whose sums are float32: no term is
   left out and no operand is narrower than ``highest`` makes it.  The
   operands enter the kernel as float32 and are cut into their terms in
   VMEM, the keys' once a call and the queries' once a tile; so that no
   lane has to move there, they enter written twice side by side (``[x |
   x]``, 128 wide) and a pass's two halves are chosen by the lane.
2. **The threshold** as ``top_k_mask`` finds it: the scores as integers
   in the floats' order (``ordered_bits``, here shifted to SIGNED integers:
   Mosaic compares those), what lies above the diagonal at the bottom of
   the order, then the ``topk``-th largest of every query by fixing 32
   bits from the top, one compare and one count over the resident tile a
   round.  Ties at the threshold go to the lower position by a second
   search over the bits of a position, entered only where some query of
   the tile has more equals than places (the tile's flag says so).
3. **The words**: key ``r`` of a block of ``bk`` is bit ``r // W`` of word
   ``r % W``, ``W = bk // 32``: 32 slabs of ``W`` rows shifted and or-ed.
   The words of the keys past the tile's last row are written zero.

A query with ``topk`` or fewer keys at or before it takes them all; a tile
of such queries alone forms no score.  Integers out: no gradient.

Measured alone on one TPU v5 lite chip (PR 45; ms a call = a layer of the
keye cell: 16,384 tokens, 16 heads of 64, 2,048 of a row): 14.19 at a tile
of 256 queries and 12.82 at 512, where ``select_keys`` takes 28.63 alone
and 37 in the step; of the 14.19 the products are ~8.5 (the MXU's pace;
with Mosaic's own ``HIGHEST`` at a contraction of 64 a call takes 22.58),
the 32 rounds 1.4, the packing 0.5.  ``PERF.md`` section 6 has the rest.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.ops import pallas_utils
from autodist_tpu.ops.topk_select import _highest_with

_use_interpret = pallas_utils.use_interpret

_WORD = 32                      # keys a word holds (``pack_selection``)
_TILE_Q = 256                   # queries a tile, where the length allows
_MIN = np.int32(-(1 << 31))     # the bottom of the signed order: unseen
_MAX = np.int32((1 << 31) - 1)
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def split_terms(x: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """float32 ``x`` as three float32 terms of eight leading bits each,
    ``x = hi + mid + lo`` EXACTLY: each is what is left cut to its leading
    bits, so each is a bfloat16 number.  Cut by a mask, not by a conversion
    there and back, which a compiler that may keep excess precision takes
    for nothing."""
    def leading(x):
        bits = lax.bitcast_convert_type(x, jnp.int32) & np.int32(-(1 << 16))
        return lax.bitcast_convert_type(bits, jnp.float32)

    hi = leading(x)
    mid = leading(x - hi)
    return hi, mid, x - hi - mid


def stacked_terms(twice: jax.Array, keys: bool) -> jax.Array:
    """``twice [rows, 2 Di] = [x | x]`` float32 to its terms stacked for
    the three passes, ``[rows, 6 Di]`` bfloat16: the keys' ``[hi|mid]
    [hi|hi] [lo|mid]`` against the queries' ``[hi|hi] [mid|lo] [hi|mid]``,
    so that ``k3 . q3`` is hi.hi + mid.hi + hi.mid + hi.lo + lo.hi +
    mid.mid, the six products of ``highest``."""
    hi, mid, lo = split_terms(twice)
    first = lax.broadcasted_iota(jnp.int32, twice.shape, 1) \
        < twice.shape[1] // 2
    passes = [jnp.where(first, hi, mid), hi, jnp.where(first, lo, mid)] \
        if keys else [hi, jnp.where(first, mid, lo),
                      jnp.where(first, hi, mid)]
    return jnp.concatenate(passes, axis=1).astype(jnp.bfloat16)


def ordered_ints(x: jax.Array) -> jax.Array:
    """float32 to int32 so that ``a < b`` as numbers is ``a < b`` as
    SIGNED integers, -0.0 counted as +0.0 (``ordered_bits(x + 0.0)`` of
    ``ops/topk_select.py`` less ``2 ** 31``)."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    bits = jnp.where(bits == _MIN, 0, bits)
    return jnp.where(bits < 0, bits ^ _MAX, bits)


def _select_kernel(qq_ref, kk_ref, wt_ref, words_ref, ties_ref, s_ref, p_ref,
                   q3_ref, k3_ref, *, topk: int, block_k: int):
    """One tile of ``bq`` queries.  Refs: qq [J, bq, 2 Di] and wt [J, bq]
    (the tile's), kk [T, 2 Di] (resident), words [T / 32, bq], ties [tiles]
    (SMEM); scratch: the tile's scores as ordered integers ``s [T, bq]``,
    the place of its last equal taken ``p [1, bq]``, and the operands'
    stacked terms, the tile's queries' ``q3 [J, bq, 6 Di]`` and, made at
    the first tile for all (the grid is sequential), the keys' ``k3 [T, 6
    Di]``."""
    i = pl.program_id(0)
    heads, bq = wt_ref.shape
    t = kk_ref.shape[0]
    per_block = block_k // _WORD
    q0 = i * bq
    # key blocks at or before the tile's last row, of all
    blocks, all_blocks = lax.div(q0 + bq + block_k - 1, block_k), t // block_k
    query = q0 + lax.broadcasted_iota(jnp.int32, (1, bq), 1)
    takes_all = query < topk         # rows with topk or fewer keys

    def rows_of(kb, size=block_k):
        return pl.ds(pl.multiple_of(kb * size, size), size)

    def over_blocks(one, init):
        return lax.fori_loop(0, blocks, one, init)

    def count(holds):
        """``[1, bq]``: the keys of the tile for which ``holds(scores
        [bk, bq], first key)`` does."""
        def one(kb, found):
            return found + jnp.sum(
                holds(s_ref[rows_of(kb), :], kb * block_k).astype(jnp.int32),
                axis=0, keepdims=True)
        return over_blocks(one, jnp.zeros((1, bq), jnp.int32))

    def highest_with(holds, bits: int):
        return _highest_with(holds, bits, (1, bq), jnp.int32)

    # bit b of a block's words: keys b W .. (b + 1) W of it
    bit = jnp.left_shift(jnp.int32(1), lax.broadcasted_iota(
        jnp.int32, (_WORD, per_block, bq), 0))

    def write_words(picked):
        """``picked(rows, key [bk, bq]) -> bool``, a key block at a time;
        zeros past the tile's last row."""
        def one(kb, carry):
            key = kb * block_k + lax.broadcasted_iota(
                jnp.int32, (block_k, bq), 0)
            taken = picked(rows_of(kb), key) & (key <= query)
            # disjoint bits: their sum is their or
            words_ref[rows_of(kb, per_block), :] = jnp.sum(
                jnp.where(taken.reshape(_WORD, per_block, bq), bit, 0),
                axis=0)
            return carry

        over_blocks(one, 0)

        def none(kb, carry):
            words_ref[rows_of(kb, per_block), :] = jnp.zeros(
                (per_block, bq), jnp.int32)
            return carry

        lax.fori_loop(blocks, all_blocks, none, 0)

    @pl.when(i == 0)
    def _():
        def cut(kb, carry):
            k3_ref[rows_of(kb), :] = stacked_terms(kk_ref[rows_of(kb), :],
                                                   keys=True)
            return carry

        lax.fori_loop(0, all_blocks, cut, 0)

    @pl.when(q0 + bq <= topk)
    def _():
        ties_ref[i] = 0
        write_words(lambda at, key: True)

    @pl.when(q0 + bq > topk)
    def _():
        for j in range(heads):
            q3_ref[j] = stacked_terms(qq_ref[j], keys=False)

        def score(kb, carry):
            keys = k3_ref[rows_of(kb), :]
            acc = jnp.zeros((block_k, bq), jnp.float32)
            for j in range(heads):
                s = lax.dot_general(keys, q3_ref[j], _NT,
                                    preferred_element_type=jnp.float32)
                acc = acc + wt_ref[j:j + 1, :] * jnp.maximum(s, 0.0)
            key = kb * block_k + lax.broadcasted_iota(
                jnp.int32, (block_k, bq), 0)
            s_ref[rows_of(kb), :] = jnp.where(key <= query,
                                              ordered_ints(acc), _MIN)
            return carry

        over_blocks(score, 0)
        # the topk-th largest: the highest value that topk keys reach
        kth = highest_with(
            lambda c: count(lambda s, _: s >= (c ^ _MIN)) >= topk,
            _WORD) ^ _MIN
        crowded = (count(lambda s, _: s >= kth) > topk) & ~takes_all
        tied = jnp.max(crowded.astype(jnp.int32)) > 0
        ties_ref[i] = tied.astype(jnp.int32)
        p_ref[...] = jnp.full((1, bq), _MAX, jnp.int32)

        @pl.when(tied)
        def _():
            # of the equals, this many are in: the highest place with
            # fewer of them before it
            places = topk - count(lambda s, _: s > kth)

            def fewer_before(c):
                return count(lambda s, first: (s == kth) & (
                    first + lax.broadcasted_iota(jnp.int32, s.shape, 0)
                    < c)) < places

            p_ref[...] = highest_with(fewer_before,
                                      max(1, (t - 1).bit_length()))

        last = p_ref[...]

        def taken(at, key):
            s = s_ref[at, :]
            return takes_all | (s > kth) | ((s == kth) & (key <= last))

        write_words(taken)


def tile_of(t: int, block_q: Optional[int] = None) -> int:
    """The queries of a tile for a sequence of ``t``."""
    return pallas_utils.pick_block(t, block_q or _TILE_Q)


def dsa_select(qi: jax.Array, ki: jax.Array, w: jax.Array, *, topk: int,
               block_k: int, block_q: Optional[int] = None,
               interpret: Optional[bool] = None) -> Tuple[jax.Array,
                                                          jax.Array]:
    """``(words [T // 32, T], ties [tiles])`` of one sequence: ``qi [T, J,
    Di]``, ``ki [T, Di]``, ``w [T, J]``.  The words are ``pack_selection``'s
    for key blocks of ``block_k`` (a divisor of ``T``, a multiple of 32)
    of ``select_keys``' selection; ``ties[i]`` is 1 where tile ``i`` of
    ``tile_of(T, block_q)`` queries searched a second time."""
    t = qi.shape[0]
    if t % block_k or block_k % _WORD:
        raise ValueError(f"{t} keys in blocks of {block_k} of {_WORD}-key "
                         f"words")
    return _dsa_select(qi, ki, w, topk=topk, block_k=block_k,
                       block_q=tile_of(t, block_q),
                       interpret=_use_interpret() if interpret is None
                       else interpret)


# Jitted: traced and lowered once a process, however many layers call it
@functools.partial(jax.jit, static_argnames=("topk", "block_k", "block_q",
                                             "interpret"))
def _dsa_select(qi, ki, w, *, topk, block_k, block_q, interpret):
    t, heads, dim = qi.shape
    tiles = t // block_q
    # written twice side by side, the queries a head at a time
    qq = jnp.moveaxis(qi, 1, 0).astype(jnp.float32)
    qq, kk = (jnp.concatenate([x, x], axis=-1)
              for x in (qq, ki.astype(jnp.float32)))
    # the scores, the keys and their terms, the tile's operands, their
    # terms and its words (what the grid brings twice: double-buffered), a
    # block's accumulator and its temporaries
    need = (4 * t * block_q + (2 * 4 * 2 + 2 * 6) * t * dim
            + block_q * ((2 * 4 * 2 + 2 * 6) * heads * dim + 2 * 4 * heads
                         + 2 * 4 * t // _WORD)
            + 6 * 4 * block_k * block_q + (2 << 20))
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, block_k=block_k),
        out_shape=(jax.ShapeDtypeStruct((t // _WORD, t), jnp.int32),
                   jax.ShapeDtypeStruct((tiles,), jnp.int32)),
        grid=(tiles,),
        in_specs=[pl.BlockSpec((heads, block_q, 2 * dim),
                               lambda i: (0, i, 0)),
                  pl.BlockSpec((t, 2 * dim), lambda i: (0, 0)),
                  pl.BlockSpec((heads, block_q), lambda i: (0, i))],
        out_specs=(pl.BlockSpec((t // _WORD, block_q), lambda i: (0, i)),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        scratch_shapes=[pltpu.VMEM((t, block_q), jnp.int32),
                        pltpu.VMEM((1, block_q), jnp.int32),
                        pltpu.VMEM((heads, block_q, 6 * dim), jnp.bfloat16),
                        pltpu.VMEM((t, 6 * dim), jnp.bfloat16)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=pallas_utils.vmem_limit(need)),
        interpret=interpret,
        name="dsa_select",
    )(qq, kk, w.T.astype(jnp.float32))
