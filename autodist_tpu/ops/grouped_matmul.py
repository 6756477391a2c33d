"""Grouped matrix products: consecutive groups of rows, each under its own
expert's weights.

The three products a routed expert layer makes (``parallel/moe.py``), as
kernels of our own in ``jax.lax.ragged_dot``'s place:

* :func:`grouped_rows`: ``rows [C, k] x weights [E, k, n] -> [C, n]``,
  group ``e``'s ``sizes[e]`` rows under ``weights[e]``;
* the same with the weights read TRANSPOSED inside the kernel
  (``transposed=True``: ``rows [C, n] x weights[e]^T -> [C, k]``, the
  rows' cotangents; no copy of an expert leaf is made for it);
* :func:`grouped_weights`: ``lhs [C, k]^T rhs [C, n] -> [E, k, n]``, each
  group's rows contracted (the weights' gradients; a group without rows
  gives zeros).

:func:`grouped_matmul` is the first with the other two as its transposes.

Operands and results cross main memory as float32 and each is read or
written ONCE a call where VMEM allows: the inner dimension stands whole in
a tile, the tile of the result's width is the widest that fits
(``pallas_utils.widest_tile``; all of it at the widths the repo runs), and
the grid walks a group's row tiles one after the other, so an expert's
weights (or, in :func:`grouped_weights`, its gradient) stay in VMEM over
them.  Tiles
are rounded to bfloat16 in VMEM and summed in float32: ONE bfloat16 pass
with float32 sums, what the MXU does at default precision; no bfloat16
array enters or leaves a kernel.  Only the row tiles that hold a row of
some group are visited (:func:`_visits`, from ``sizes`` on the device, read
by the index maps as prefetched scalars); a tile that spans two groups is
visited once a group and masked.  Rows past the last group come back
UNWRITTEN.
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

#: rows of a tile, where the rows are a whole number of them (the chunks of
#: ``parallel/moe.py: chunk_rows`` are)
ROW_TILE = 512
#: rows of a part of a tile that a group does not fill (two groups share it,
#: or the last group ends in it): only the parts that hold a row of the
#: group are computed
_PART = 128
_VMEM = 92 << 20          # bytes a kernel's blocks and values may take
_ROOM = 4 << 20           # of them, for what the estimates leave out

_NN = (((1,), (0,)), ((), ()))     # [m, k] x [k, n]
_NT = (((1,), (1,)), ((), ()))     # [m, n] x [k, n]^T
_TN = (((0,), (0,)), ((), ()))     # [m, k]^T x [m, n]


def _row_tile(rows: int):
    """(the rows of a tile, the rows of a part of it)."""
    tile = math.gcd(rows, ROW_TILE)
    if tile % pallas_utils.SUBLANE:
        return rows, rows
    part = math.gcd(tile, _PART)
    return tile, tile if part % pallas_utils.SUBLANE else part


def _visits(sizes: jax.Array, rows: int, tile: int, empty_groups: bool):
    """The (row tile, group) pairs a kernel visits, in order, for groups of
    ``sizes`` consecutive rows of ``rows``: ``(where each group starts and
    the last ends [E + 1], the group [V] and the row tile [V] of each
    visit, how many visits there are [1])``, ``V = rows / tile + E - 1``
    the most there can be.  A group is visited on every tile that holds a
    row of it, one tile after the other; a group without rows once, on any
    tile, if ``empty_groups`` (its gradient has to be written) and else not
    at all.  The entries past the last visit repeat it: nothing is fetched
    for them."""
    count = sizes.shape[0]
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    starts = ends - sizes
    first = starts // tile
    spans = jnp.where(sizes > 0, (ends - 1) // tile - first + 1,
                      int(empty_groups))
    upto = jnp.cumsum(spans)
    visit = jnp.minimum(jnp.arange(rows // tile + count - 1),
                        jnp.maximum(upto[-1] - 1, 0))
    group = jnp.minimum(jnp.sum(visit[:, None] >= upto, axis=1), count - 1)
    # one number of E a visit, by a comparison with every group
    of_group = group[:, None] == jnp.arange(count)
    tiles = visit + jnp.sum(jnp.where(of_group, first - (upto - spans), 0),
                            axis=1)
    return (jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]),
            group.astype(jnp.int32),
            jnp.clip(tiles, 0, rows // tile - 1).astype(jnp.int32),
            upto[-1:].astype(jnp.int32))


def row_tiles(sizes: jax.Array, rows: int):
    """``(visited, live)``: the (row tile, group) pairs :func:`grouped_rows`
    is scheduled on for groups of ``sizes [..., E]`` rows of ``rows``, and
    the row tiles that hold a row of some group; int32, ``sizes``' leading
    shape.  The same integers the kernels' visits are made of."""
    tile, _ = _row_tile(rows)
    ends = jnp.cumsum(sizes.astype(jnp.int32), axis=-1)
    spans = jnp.where(sizes > 0,
                      (ends - 1) // tile - (ends - sizes) // tile + 1, 0)
    return spans.sum(axis=-1), -(-ends[..., -1] // tile)


def _visit(offsets_ref, groups_ref, tiles_ref, visits_ref, tile: int):
    """Of this grid step (read at the kernel's top level: the interpreter
    knows no ``program_id`` under a ``when``): ``(is it a visit, is its
    group another than the last visit's, the rows of its tile the group
    holds: from, to)``."""
    v = pl.program_id(1)
    group = groups_ref[v]
    first = tiles_ref[v] * tile
    return (v < visits_ref[0],
            (v == 0) | (groups_ref[jnp.maximum(v - 1, 0)] != group),
            offsets_ref[group] - first, offsets_ref[group + 1] - first)


def _parts(start, end, tile: int, part: int, one):
    """``one(j, 0)`` for the parts of a tile that hold a row of ``[start,
    end)``."""
    jax.lax.fori_loop(jnp.maximum(start, 0) // part,
                      -(-jnp.minimum(end, tile) // part), one, 0)


def _rows_kernel(offsets_ref, groups_ref, tiles_ref, visits_ref, rows_ref,
                 weights_ref, out_ref, rounded_ref, *, tile, part, dims):
    visit, new_group, start, end = _visit(offsets_ref, groups_ref, tiles_ref,
                                          visits_ref, tile)

    def product(rows):
        return jax.lax.dot_general(
            rows.astype(jnp.bfloat16), rounded_ref[...], dims,
            preferred_element_type=jnp.float32)

    @pl.when(visit)
    def _():
        @pl.when(new_group)
        def _():        # once a group, not once a row tile
            rounded_ref[...] = weights_ref[...].astype(jnp.bfloat16)

        whole = (start <= 0) & (end >= tile)

        @pl.when(whole)
        def _():
            out_ref[...] = product(rows_ref[...])

        @pl.when(jnp.logical_not(whole))
        def _():        # the other rows are another group's, or nobody's
            def one(j, carry):
                at = pl.ds(pl.multiple_of(j * part, part), part)
                row = j * part + jax.lax.broadcasted_iota(
                    jnp.int32, (part, out_ref.shape[1]), 0)
                out_ref[at, :] = jnp.where(
                    (row >= start) & (row < end), product(rows_ref[at, :]),
                    out_ref[at, :])
                return carry

            _parts(start, end, tile, part, one)


def grouped_rows(rows: jax.Array, weights: jax.Array, sizes: jax.Array, *,
                 transposed: bool = False) -> jax.Array:
    """``[C, n]``: rows ``sum(sizes[:e]) .. sum(sizes[:e + 1])`` of ``rows
    [C, k]`` times ``weights[e] [k, n]``, for every group ``e`` of ``sizes
    [E]`` (int32, on the device, summing to ``C`` at most); what lies past
    the last group is UNWRITTEN.  ``transposed``: ``rows [C, n]`` times
    ``weights[e]^T``, ``[C, k]``."""
    tile, part = _row_tile(rows.shape[0])
    return _grouped_rows(rows, weights, sizes, transposed=transposed,
                         tile=tile, part=part, interpret=_use_interpret())


# Jitted, here and below: a step calls each kind at one or two shapes 24
# times an expert layer (gate and up, every layer, the first chunk and the
# loop's body, forward and backward), and tracing and lowering a kernel
# costs a tenth of a second of EVERY run's set-up, warm cache or not; the
# calls of one shape are traced and lowered once.
@functools.partial(jax.jit, static_argnames=("transposed", "tile", "part",
                                             "interpret"))
def _grouped_rows(rows, weights, sizes, *, transposed, tile, part, interpret):
    count, inner = rows.shape
    wide = weights.shape[1 if transposed else 2]
    assert weights.shape[2 if transposed else 1] == inner, (
        rows.shape, weights.shape, transposed)

    def need(w):        # bytes in VMEM at a tile of the result ``w`` wide
        # rows, weights and result twice (double-buffered), the weights
        # rounded, and the values: the rows rounded, the product
        return ((2 * 4 + 2) * tile * inner + (2 * 4 + 2) * inner * w
                + (2 * 4 + 4) * tile * w + _ROOM)

    width = pallas_utils.widest_tile(wide, lambda w: need(w) <= _VMEM)
    block = (width, inner) if transposed else (inner, width)

    def weights_block(j, v, offsets_ref, groups_ref, tiles_ref, visits_ref):
        return (groups_ref[v], j, 0) if transposed else (groups_ref[v], 0, j)

    return pl.pallas_call(
        functools.partial(_rows_kernel, tile=tile, part=part,
                          dims=_NT if transposed else _NN),
        out_shape=jax.ShapeDtypeStruct((count, wide), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(wide // width, count // tile + sizes.shape[0] - 1),
            in_specs=[
                pl.BlockSpec((tile, inner),
                             lambda j, v, o, g, tiles_ref, n: (
                                 tiles_ref[v], 0)),
                pl.BlockSpec((None,) + block, weights_block)],
            out_specs=pl.BlockSpec(
                (tile, width),
                lambda j, v, o, g, tiles_ref, n: (tiles_ref[v], j)),
            scratch_shapes=[pltpu.VMEM(block, jnp.bfloat16)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=pallas_utils.vmem_limit(need(width))),
        interpret=interpret,
        name="grouped_rows_t" if transposed else "grouped_rows",
    )(*_visits(sizes, count, tile, empty_groups=False),
      rows.astype(jnp.float32), weights.astype(jnp.float32)
      ).astype(rows.dtype)


def _weights_kernel(offsets_ref, groups_ref, tiles_ref, visits_ref, lhs_ref,
                    rhs_ref, out_ref, *, tile, part):
    visit, new_group, start, end = _visit(offsets_ref, groups_ref, tiles_ref,
                                          visits_ref, tile)

    def add(lhs, rhs):
        out_ref[...] += jax.lax.dot_general(
            lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16), _TN,
            preferred_element_type=jnp.float32)

    @pl.when(visit)
    def _():
        @pl.when(new_group)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        whole = (start <= 0) & (end >= tile)

        @pl.when(whole)
        def _():
            add(lhs_ref[...], rhs_ref[...])

        @pl.when(jnp.logical_not(whole))
        def _():        # the other rows are another group's, or unwritten
            def one(j, carry):
                at = pl.ds(pl.multiple_of(j * part, part), part)

                def held(ref):
                    row = j * part + jax.lax.broadcasted_iota(
                        jnp.int32, (part, ref.shape[1]), 0)
                    return jnp.where((row >= start) & (row < end),
                                     ref[at, :], 0)

                add(held(lhs_ref), held(rhs_ref))
                return carry

            _parts(start, end, tile, part, one)


def grouped_weights(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array
                    ) -> jax.Array:
    """``[E, k, n]``: for every group ``e`` of ``sizes [E]``, its rows of
    ``lhs [C, k]`` transposed times its rows of ``rhs [C, n]``; zeros for a
    group without rows.  What lies past the last group is never read as
    numbers."""
    tile, part = _row_tile(lhs.shape[0])
    return _grouped_weights(lhs, rhs, sizes, tile=tile, part=part,
                            interpret=_use_interpret())


@functools.partial(jax.jit, static_argnames=("tile", "part", "interpret"))
def _grouped_weights(lhs, rhs, sizes, *, tile, part, interpret):
    count, k = lhs.shape
    n = rhs.shape[1]
    assert rhs.shape[0] == count, (lhs.shape, rhs.shape)

    def need(w):        # bytes in VMEM at a tile of the result ``w`` wide
        # the operands and the result twice, and the values: the operands
        # rounded (one of them turned), the product
        return ((2 * 4 + 2 + 2) * tile * k + (2 * 4 + 2) * tile * w
                + (2 * 4 + 4) * k * w + _ROOM)

    width = pallas_utils.widest_tile(n, lambda w: need(w) <= _VMEM)
    return pl.pallas_call(
        functools.partial(_weights_kernel, tile=tile, part=part),
        out_shape=jax.ShapeDtypeStruct((sizes.shape[0], k, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // width, count // tile + sizes.shape[0] - 1),
            in_specs=[
                pl.BlockSpec((tile, k),
                             lambda j, v, o, g, tiles_ref, n: (
                                 tiles_ref[v], 0)),
                pl.BlockSpec((tile, width),
                             lambda j, v, o, g, tiles_ref, n: (
                                 tiles_ref[v], j))],
            out_specs=pl.BlockSpec(
                (None, k, width),
                lambda j, v, o, groups_ref, t, n: (groups_ref[v], 0, j))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=pallas_utils.vmem_limit(need(width))),
        interpret=interpret,
        name="grouped_weights",
    )(*_visits(sizes, count, tile, empty_groups=True),
      lhs.astype(jnp.float32), rhs.astype(jnp.float32)).astype(lhs.dtype)


@jax.custom_vjp
def grouped_matmul(rows: jax.Array, weights: jax.Array, sizes: jax.Array
                   ) -> jax.Array:
    """:func:`grouped_rows` of ``rows [C, k]`` and ``weights [E, k, n]``,
    differentiable: the rows' cotangent is the kernel that reads the
    weights transposed, the weights' is :func:`grouped_weights`."""
    return grouped_rows(rows, weights, sizes)


def _grouped_matmul_fwd(rows, weights, sizes):
    return grouped_rows(rows, weights, sizes), (rows, weights, sizes)


def _grouped_matmul_bwd(kept, g):
    rows, weights, sizes = kept
    return (grouped_rows(g, weights, sizes, transposed=True),
            grouped_weights(rows, g, sizes), None)


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
