"""Rows in another order added up by token: ``y[t] = sum of scale[r] *
rows[r]`` over the LIVE rows ``r`` whose ``token[r]`` is ``t``.

The return of a routed expert layer's sorted rows to token order
(``parallel/moe.py``).  As XLA operations it is a gather of every pick's
row and a sum over the picks, ``N * k`` rows wide however few of them are
held here; as a scatter-add XLA walks the updates one at a time.  This
kernel reads the live rows once and writes the tokens once: the grid runs
over tiles of the width and blocks of the rows, a tile's ``[tokens,
width]`` block stays in VMEM over the blocks of rows, and one loop over a
block's live rows adds each to its token's row, tokens and scales read as
scalars.  Rows from ``live`` on are never read as numbers (a grouped
product leaves them unwritten) and their blocks are not fetched.

Measured alone on one TPU v5 lite chip (PR 37; ms a call, float32, ~3,000
of the rows live).  ``[6144, 2560] -> [4096, 2560]``: 0.36 at a width of
256, 0.23 at 512 and at 640, 0.21 at 1,280 (what :func:`_width` gives) and
at 2,560, where the parent's gather, select, relayout and sum over six
picks take 1.86, the gather and the sum with the picks leading 0.89 and a
scatter-add 2.22.  ``[8192, 2048] -> [4096, 2048]``: 0.37 at 256, 0.22 at
512, 0.21 at 1,024, 0.20 at 2,048 (given; 0.88, 0.93, 0.91).  With all
24,576 or 32,768 rows live 1.49 and 1.62 at 512 (2.65 and 1.76, 2.35 and
2.52, 4.60 and 3.49).  Blocks of 256 rows read as blocks of 512 do.  A
call costs its live rows times the TILES of the width (each row is visited
once a tile): since PR 39 a routed layer hands it all 16,384 tokens of a
step, the resident block is 32 MiB so that the tile is 512 wide, and in
the smallthinker step a call over ~12,300 live rows of ``[24576, 2560]``
takes 0.9 ms (1.46 at the tile of 256 that 16 MiB gave; my chip runs, PR
39).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.ops import pallas_utils

_use_interpret = pallas_utils.use_interpret

_BLOCK = 512                 # sorted rows a grid step
_RESIDENT = 32 << 20         # bytes of the tokens' block that stays in VMEM


def _width(tokens: int, d: int) -> int:
    """The widest tile of ``d`` whose ``[tokens, tile]`` float32 block is
    within :data:`_RESIDENT`."""
    return pallas_utils.widest_tile(
        d, lambda w: tokens * w * 4 <= _RESIDENT)


def _accumulate(live_ref, token_ref, scale_ref, rows_ref, *onto_ref_and_y_ref,
                block):
    *onto_ref, y_ref = onto_ref_and_y_ref       # the carried block, if any
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        y_ref[...] = onto_ref[0][...] if onto_ref else jnp.zeros_like(y_ref)

    first = i * block

    def add(r, carry):
        t = token_ref[first + r]
        y_ref[pl.ds(t, 1), :] += (rows_ref[pl.ds(r, 1), :]
                                  * scale_ref[first + r])
        return carry

    jax.lax.fori_loop(0, jnp.clip(live_ref[0] - first, 0, block), add, 0)


def rows_to_tokens(rows: jax.Array, token: jax.Array, scale: jax.Array,
                   live: jax.Array, tokens: int,
                   onto: Optional[jax.Array] = None) -> jax.Array:
    """``[tokens, d]``: row ``t`` is the sum, in the order of the rows, of
    ``scale[r] * rows[r]`` over ``r < live`` with ``token[r] == t`` (zeros
    where there is none), added up in float32.  ``rows [R, d]``, ``token
    [R]`` int32 in ``[0, tokens)``, ``scale [R]``, ``live`` an int32 scalar
    on the device.  ``onto [tokens, d]``: what the rows are added onto, one
    after the other, in zeros' place (the sum carried over the chunks of a
    routed layer's sorted order; its buffer is the result's); its block
    stays in VMEM beside the result's, so the tile is half as wide.
    ``token`` and ``scale`` are held as scalars (SMEM) whole: call it with
    a few ten thousand rows, as a routed layer's call has."""
    count, d = rows.shape
    blocks = 1 if onto is None else 2
    width = _width(blocks * tokens, d)
    block = math.gcd(count, _BLOCK)
    if block % pallas_utils.SUBLANE:
        block = count
    # every block twice (double-buffered), and room for the loop's values
    need = 2 * 4 * (blocks * tokens + block) * width + (2 << 20)

    def live_block(j, i, live_ref, token_ref, scale_ref):
        # blocks past the last live row name it again: not fetched
        return jnp.minimum(i, jnp.maximum(live_ref[0] - 1, 0) // block), j

    resident = pl.BlockSpec((tokens, width), lambda j, i, *_: (0, j))
    carried = () if onto is None else (onto.astype(jnp.float32),)
    return pl.pallas_call(
        functools.partial(_accumulate, block=block),
        out_shape=jax.ShapeDtypeStruct((tokens, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(d // width, count // block),
            in_specs=[pl.BlockSpec((block, width), live_block)]
            + [resident] * len(carried),
            out_specs=resident),
        # operand 4, after the three scalars and the rows
        input_output_aliases={4: 0} if carried else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=pallas_utils.vmem_limit(need)),
        interpret=_use_interpret(),
        name="rows_to_tokens",
    )(live.reshape(1).astype(jnp.int32), token.astype(jnp.int32),
      scale.astype(jnp.float32), rows.astype(jnp.float32),
      *carried).astype(rows.dtype)
