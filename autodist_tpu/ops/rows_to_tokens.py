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
256, 0.23 at 512 and at 640 (what :func:`_width` gives), 0.21 at 1,280 and
at 2,560, where the parent's gather, select, relayout and sum over six
picks take 1.86, the gather and the sum with the picks leading 0.89 and a
scatter-add 2.22.  ``[8192, 2048] -> [4096, 2048]``: 0.37 at 256, 0.22 at
512, 0.21 at 1,024 (given), 0.20 at 2,048 (0.88, 0.93, 0.91).  With all
24,576 or 32,768 rows live 1.49 and 1.62 at 512 (2.65 and 1.76, 2.35 and
2.52, 4.60 and 3.49).  Blocks of 256 rows read as blocks of 512 do.  In
the smallthinker step a call takes 0.22 (forward) and 0.25 ms (backward).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.ops import pallas_utils

_use_interpret = pallas_utils.use_interpret

_BLOCK = 512                 # sorted rows a grid step
_RESIDENT = 16 << 20         # bytes of the tokens' block that stays in VMEM


def _width(tokens: int, d: int) -> int:
    """The widest tile of ``d`` (whole 128-lane tiles that divide it) whose
    ``[tokens, tile]`` float32 block is within :data:`_RESIDENT`; all of
    ``d`` where it is no whole number of lanes."""
    if d % pallas_utils.TILE:
        return d
    fits = [w for w in range(pallas_utils.TILE, d + 1, pallas_utils.TILE)
            if d % w == 0 and tokens * w * 4 <= _RESIDENT]
    return fits[-1] if fits else pallas_utils.TILE


def _accumulate(live_ref, token_ref, scale_ref, rows_ref, y_ref, *, block):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    first = i * block

    def add(r, carry):
        t = token_ref[first + r]
        y_ref[pl.ds(t, 1), :] += (rows_ref[pl.ds(r, 1), :]
                                  * scale_ref[first + r])
        return carry

    jax.lax.fori_loop(0, jnp.clip(live_ref[0] - first, 0, block), add, 0)


def rows_to_tokens(rows: jax.Array, token: jax.Array, scale: jax.Array,
                   live: jax.Array, tokens: int) -> jax.Array:
    """``[tokens, d]``: row ``t`` is the sum, in the order of the rows, of
    ``scale[r] * rows[r]`` over ``r < live`` with ``token[r] == t`` (zeros
    where there is none), added up in float32.  ``rows [R, d]``, ``token
    [R]`` int32 in ``[0, tokens)``, ``scale [R]``, ``live`` an int32 scalar
    on the device.  ``token`` and ``scale`` are held as scalars (SMEM) whole:
    call it with a few ten thousand rows, as a routed layer's call has."""
    count, d = rows.shape
    width = _width(tokens, d)
    block = math.gcd(count, _BLOCK)
    if block % pallas_utils.SUBLANE:
        block = count
    # both blocks twice (double-buffered), and room for the loop's values
    need = 2 * 4 * (tokens + block) * width + (2 << 20)

    def live_block(j, i, live_ref, token_ref, scale_ref):
        # blocks past the last live row name it again: not fetched
        return jnp.minimum(i, jnp.maximum(live_ref[0] - 1, 0) // block), j

    return pl.pallas_call(
        functools.partial(_accumulate, block=block),
        out_shape=jax.ShapeDtypeStruct((tokens, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(d // width, count // block),
            in_specs=[pl.BlockSpec((block, width), live_block)],
            out_specs=pl.BlockSpec((tokens, width), lambda j, i, *_: (0, j))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=pallas_utils.vmem_limit(need)),
        interpret=_use_interpret(),
        name="rows_to_tokens",
    )(live.reshape(1).astype(jnp.int32), token.astype(jnp.int32),
      scale.astype(jnp.float32), rows.astype(jnp.float32)).astype(rows.dtype)
