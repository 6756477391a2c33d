"""What stands between a Gated DeltaNet mixer's projection and its
recurrence, as ONE pair of Pallas kernels: the short causal convolution of
q, k, v with its silu, the l2norm of q and k, and the split of ``qkvz``.

``qkvz [B, T, Hk, (2 + 2 r) Dl]`` holds, a key head, ``Dl`` columns of q,
``Dl`` of k, ``r Dl`` of v and ``r Dl`` of z (``r`` value heads a key
head).  With ``w [.., K]`` a channel's taps,

    c_t = silu(sum_{i<K} w[:, i] * [q k v]_{t-K+1+i})      zeros before t = 0
    q = l2norm(c_q) * scale    k = l2norm(c_k)    v = c_v    z = z

(``models/gdn_moe_lm.py: conv_qkvz`` is these lines in ``jax.numpy``: what
runs off a TPU and what ``tests/test_gdn_conv.py`` holds the kernels to).
As XLA operations the convolution is a pad and ``K`` shifted slices a
tensor, silu, the norms and the split each a pass of their own: q, k, v of
one sequence cross main memory some twenty-six times forward, recomputed
and backward where seven would do (issue 48).

**Layout.**  The kernels take ``qkvz`` as ``[B, Hk, T, C]``, a head's
tokens one after the other, and hand q, k, v, z back so: it is how the
projection's product writes ``qkvz`` and how the recurrence reads q, k, v
(XLA gives the ``[B, T, H, ..]`` arrays around the op that layout of its
own accord, so :func:`_by_head` moves nothing; with the tokens leading and
``[heads, columns]`` the tiles, the first form of this file, the compiled
step copied all of ``qkvz`` into the kernels' layout before every call,
``tests/test_flash_tpu_compile.py`` now asserts it does not).  Tokens lie
along the sublanes and a head's columns along the lanes, so a value head
is a block of its own and nothing is stored strided; a token ``i`` earlier
is a slice of the window ``i`` sublanes off the tiles' grid, which Mosaic
makes by rotating.

**Forward** (HLO name ``gdn_conv``): the grid runs over (sequence, key
head, tile of tokens), the tiles last and in order.  A step reads its
``[tile, C]`` block of ``qkvz`` IN PLACE, takes the ``_HALO`` rows before
it from VMEM scratch (the tile before left them there; zeros at ``t =
0``), and writes ``q, k [B, Hk, T, Dl]`` and ``v, z [B, r Hk, T, Dl]``:
every element is read once and written once.  A loop takes ``_ROWS``
tokens and one ``Dl``-wide slab of columns at a time, so that its values
stay in registers.

**Backward** (HLO name ``gdn_conv_bwd``) keeps no residual but the
operands: from ``qkvz`` it forms the pre-activation, the silu and the norms
again in VMEM, from ``dq, dk, dv`` the pre-activation's cotangent

    a = silu(p)    r = rsqrt(sum a^2 + eps)    y = c a r  (c: scale or 1)
    da = c r (dy - a r^2 sum(dy a))           dp = da s (1 + p (1 - s))

with ``s = sigmoid(p)``, and walks the tiles IN REVERSE with the later
tile's first rows of ``dp`` carried in scratch (``dx_t = sum_i w[:, i]
dp_{t+K-1-i}``; the rows of ``x`` before a tile come as a second small
block of the same operand).  It writes the cotangent of ALL of ``qkvz``
once, ``dz`` in its columns, and sums ``dw[c, i] = sum_t dp[t, c]
x[t-K+1+i, c]`` in an output block that stays in VMEM over a (sequence,
head)'s tiles, a row for each sublane; the sums over sublanes and
sequences are taken outside.

That z passes through the op is what lets the backward write ``qkvz``'s
cotangent whole: handed q, k, v alone, autodiff adds the op's padded ``dx``
to z's padded cotangent in a pass of its own over the widest array of the
layer.  All arithmetic is float32 and no product is formed.

Measured alone on one TPU v5 lite chip at the qwen3-next cell's shapes
(``[1, 16, 8192, 768]``, my chip run, PR 48; ms a call): forward 1.27 for
a least time of 0.98 from the bytes (402 MB each way at 819 GB/s),
backward 1.97 for 1.47, at tiles of 512 and turns of 64 tokens; tiles of
256 to 2,048 read the same to 3 %, turns of 32 tokens 2.10 backward, of 16
1.50 and 2.70, of 8 2.40 and 4.62.  The plain lines compiled alone take
4.02 forward and 10.19 forward and backward.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.ops import pallas_utils

_use_interpret = pallas_utils.use_interpret

#: the kernels' HLO names
KERNEL_NAME = "gdn_conv"
BWD_KERNEL_NAME = "gdn_conv_bwd"
EPS = 1e-6                  # l2norm's, inside the root
_TILE = 512                 # tokens a grid step
_ROWS = 64                  # tokens a turn of the loop inside a step
_HALO = pallas_utils.SUBLANE    # rows kept of the tile before: whole sublanes


def _slabs(dl: int, share: int):
    """The ``Dl``-wide slabs of a key head's convolved columns: ``(slab,
    its columns)``; 0 is q, 1 is k, the others v's value heads."""
    return [(s, slice(s * dl, (s + 1) * dl)) for s in range(2 + share)]


def _turns(tile: int, rows: int, turn, apart: bool = False):
    """``turn(first row)`` over a tile's rows ``rows`` at a time; ``apart``:
    the first turn traced on its own, its ``first`` a Python 0 (its window
    begins in the rows before the tile)."""
    def body(i, carry):
        turn(pl.multiple_of(i * rows, _HALO))
        return carry

    if apart:
        turn(0)
    if tile // rows > apart:      # else what is traced may not fit a tile
        jax.lax.fori_loop(int(apart), tile // rows, body, 0)


def _window(x_ref, before, first, rows: int, cols):
    """``[_HALO + rows, Dl]``: the tile's rows ``first - _HALO .. first +
    rows`` of ``cols``.  The first turn's (``first`` a Python 0) begins
    with ``before(cols) [_HALO, Dl]``, the rows before the tile."""
    if isinstance(first, int):
        return jnp.concatenate([before(cols), x_ref[0, 0, :rows, cols]],
                               axis=0)
    return x_ref[0, 0, pl.ds(first - _HALO, _HALO + rows), cols]


def _shifted(win, back: int, rows: int):
    """Rows ``_HALO - back .. _HALO - back + rows`` of a window: the turn's
    rows, each ``back`` tokens earlier."""
    return win[_HALO - back:_HALO - back + rows]


def _conv(win, w_ref, cols, rows: int):
    """The turn's pre-activations ``[rows, Dl]`` from its window."""
    taps = w_ref.shape[1]
    return sum(_shifted(win, taps - 1 - i, rows) * w_ref[0, i:i + 1, cols]
               for i in range(taps))


def _fwd_kernel(x_ref, w_ref, q_ref, k_ref, v_ref, z_ref, halo_ref, *,
                dl: int, share: int, scale: float, rows: int):
    """One tile of one (sequence, key head): ``x [1, 1, tile, C]``, ``w [1,
    K, Cc]``, ``q, k [1, 1, tile, Dl]``, ``v, z [1, r, tile, Dl]``; scratch
    ``halo [_HALO, Cc]``, the rows before the tile."""
    tile = x_ref.shape[2]
    convolved = (2 + share) * dl

    @pl.when(pl.program_id(2) == 0)
    def _():
        halo_ref[...] = jnp.zeros_like(halo_ref)

    def turn(first):
        at = pl.ds(first, rows)
        for slab, cols in _slabs(dl, share):
            win = _window(x_ref, lambda c: halo_ref[:, c], first, rows, cols)
            act = jax.nn.silu(_conv(win, w_ref, cols, rows))
            if slab < 2:
                act = act * jax.lax.rsqrt(
                    jnp.sum(act * act, -1, keepdims=True) + EPS)
            if slab == 0:
                q_ref[0, 0, at] = act * scale
            elif slab == 1:
                k_ref[0, 0, at] = act
            else:
                v_ref[0, slab - 2, at] = act
        for s in range(share):
            z_ref[0, s, at] = x_ref[
                0, 0, at, convolved + s * dl:convolved + (s + 1) * dl]

    _turns(tile, rows, turn, apart=True)
    halo_ref[...] = x_ref[0, 0, tile - _HALO:, :convolved]


def _bwd_kernel(x_ref, xp_ref, w_ref, dq_ref, dk_ref, dv_ref, dz_ref, dx_ref,
                dw_ref, dp_ref, *, dl: int, share: int, scale: float,
                rows: int, t: int):
    """One tile of one (sequence, key head), the tiles in reverse: ``xp [1,
    1, _HALO, C]`` ends where the tile begins; ``dx`` as ``x``; ``dw [1, 1,
    K, _HALO, Cc]`` the sums over the tiles so far, a row of it the rows
    that many past a whole number of sublanes; scratch ``dp [tile + _HALO,
    Cc]``, the pre-activation's cotangent over the tile and the rows after
    it."""
    taps, tile = w_ref.shape[1], x_ref.shape[2]
    convolved = (2 + share) * dl
    j = pl.program_id(2)
    first_row = (pl.num_programs(2) - 1 - j) * tile   # of the sequence

    @pl.when(j == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    # the later tile's first rows, zeros after the sequence's last
    dp_ref[tile:] = jnp.where(j > 0, dp_ref[:_HALO], 0.0)

    def real(first, count, x):
        """``x`` with zeros in the rows past the sequence's end (what a
        tile that ``t`` does not fill holds there is no number)."""
        if t % tile == 0:
            return x
        row = first_row + first + jax.lax.broadcasted_iota(
            jnp.int32, (count, 1), 0)
        return jnp.where(row < t, x, 0.0)

    def before(cols):
        return jnp.where(first_row > 0, xp_ref[0, 0, :, cols], 0.0)

    def turn(first):
        at = pl.ds(first, rows)
        for slab, cols in _slabs(dl, share):
            win = real(first - _HALO, _HALO + rows,
                       _window(x_ref, before, first, rows, cols))
            pre = _conv(win, w_ref, cols, rows)
            gate = jax.nn.sigmoid(pre)
            act = pre * gate
            if slab < 2:
                dy = (dq_ref, dk_ref)[slab][0, 0, at]
                inv = jax.lax.rsqrt(jnp.sum(act * act, -1, keepdims=True)
                                    + EPS)
                dact = inv * (dy - act * (inv * inv * jnp.sum(
                    dy * act, -1, keepdims=True)))
                if slab == 0:
                    dact = dact * scale
            else:
                dact = dv_ref[0, slab - 2, at]
            dpre = real(first, rows,
                        dact * (gate * (1.0 + pre * (1.0 - gate))))
            dp_ref[at, cols] = dpre
            for i in range(taps):
                each = dpre * _shifted(win, taps - 1 - i, rows)
                dw_ref[0, 0, i, :, cols] += sum(
                    each[m:m + _HALO] for m in range(0, rows, _HALO))

    _turns(tile, rows, turn, apart=True)

    def back(first):
        at = pl.ds(first, rows)
        for _, cols in _slabs(dl, share):
            dwin = dp_ref[pl.ds(first, rows + _HALO), cols]
            dx_ref[0, 0, at, cols] = sum(
                dwin[taps - 1 - i:taps - 1 - i + rows]
                * w_ref[0, i:i + 1, cols] for i in range(taps))
        for s in range(share):
            dx_ref[0, 0, at, convolved + s * dl:convolved + (s + 1) * dl] = \
                dz_ref[0, s, at]

    _turns(tile, rows, back)


def _blocks(qkvz, dl: int, tile: int):
    """``(tile, rows a turn, share, grid)`` for ``qkvz [B, Hk, T, C]``: whole
    sublanes a turn, whole turns a tile."""
    b, hk, t, c = qkvz.shape
    tile = min(tile, pallas_utils.pad_to(t, _HALO))
    rows = max(r for r in range(_HALO, min(_ROWS, tile) + 1, _HALO)
               if tile % r == 0)
    return tile, rows, c // (2 * dl) - 1, (b, hk, pl.cdiv(t, tile))


def _params(tile: int, c: int, blocks: int, scratch: int):
    """``blocks`` float32 ``[tile, c]`` a grid step, each held twice
    (double-buffered), beside ``scratch`` bytes and the loop's values."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=pallas_utils.vmem_limit(
            2 * 4 * blocks * tile * c + scratch + (4 << 20)))


# Jitted: traced and lowered once a shape, however many layers call it
@functools.partial(jax.jit, static_argnames=("dl", "scale", "tile",
                                             "interpret"))
def _forward(qkvz, w, *, dl, scale, tile, interpret):
    """``qkvz [B, Hk, T, C]``, ``w [Hk, K, Cc]``: ``(q, k [B, Hk, T, Dl],
    v, z [B, r Hk, T, Dl])``."""
    b, hk, t, c = qkvz.shape
    _, taps, convolved = w.shape
    tile, rows, share, grid = _blocks(qkvz, dl, tile)

    def rows_of(heads, width):
        return pl.BlockSpec((1, heads, tile, width),
                            lambda i, h, j: (i, h, j, 0))

    def out(heads):
        return jax.ShapeDtypeStruct((b, heads, t, dl), jnp.float32)

    return pl.pallas_call(
        functools.partial(_fwd_kernel, dl=dl, share=share, scale=scale,
                          rows=rows),
        out_shape=(out(hk), out(hk), out(share * hk), out(share * hk)),
        grid=grid,
        in_specs=[rows_of(1, c),
                  pl.BlockSpec((1, taps, convolved),
                               lambda i, h, j: (h, 0, 0))],
        out_specs=(rows_of(1, dl), rows_of(1, dl), rows_of(share, dl),
                   rows_of(share, dl)),
        scratch_shapes=[pltpu.VMEM((_HALO, convolved), jnp.float32)],
        compiler_params=_params(tile, c, 2, 0),
        interpret=interpret,
        name=KERNEL_NAME,
    )(qkvz.astype(jnp.float32), w)


@functools.partial(jax.jit, static_argnames=("dl", "scale", "tile",
                                             "interpret"))
def _backward(qkvz, w, dq, dk, dv, dz, *, dl, scale, tile, interpret):
    """``(dqkvz, dw [Hk, K, Cc])`` from the cotangents in the forward's
    layout."""
    b, hk, t, c = qkvz.shape
    _, taps, convolved = w.shape
    tile, rows, share, grid = _blocks(qkvz, dl, tile)
    tiles = grid[2]

    def back(i, h, j):
        return i, h, tiles - 1 - j, 0

    def before(i, h, j):     # the sublanes that end where the tile begins
        return i, h, jnp.maximum((tiles - 1 - j) * (tile // _HALO) - 1, 0), 0

    def rows_of(heads, width):
        return pl.BlockSpec((1, heads, tile, width), back)

    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, dl=dl, share=share, scale=scale,
                          rows=rows, t=t),
        out_shape=(jax.ShapeDtypeStruct(qkvz.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, hk, taps, _HALO, convolved),
                                        jnp.float32)),
        grid=grid,
        in_specs=[rows_of(1, c), pl.BlockSpec((1, 1, _HALO, c), before),
                  pl.BlockSpec((1, taps, convolved),
                               lambda i, h, j: (h, 0, 0)),
                  rows_of(1, dl), rows_of(1, dl), rows_of(share, dl),
                  rows_of(share, dl)],
        out_specs=(rows_of(1, c),
                   pl.BlockSpec((1, 1, taps, _HALO, convolved),
                                lambda i, h, j: (i, h, 0, 0, 0))),
        scratch_shapes=[pltpu.VMEM((tile + _HALO, convolved), jnp.float32)],
        compiler_params=_params(tile, c, 3, 4 * (tile + _HALO) * convolved),
        interpret=interpret,
        name=BWD_KERNEL_NAME,
    )(qkvz.astype(jnp.float32), qkvz.astype(jnp.float32), w,
      *(d.astype(jnp.float32) for d in (dq, dk, dv, dz)))
    return dx, dw.sum(axis=(0, 3))


def _taps(w_q, w_k, w_v):
    """``[Hk, K, Cc]`` float32: the taps in the order of a key head's
    convolved columns."""
    return jnp.swapaxes(jnp.concatenate([w_q, w_k, w_v], axis=1), 1,
                        2).astype(jnp.float32)


def _by_head(x):
    """``[B, T, H, ..] <-> [B, H, T, ..]``: the kernels' layout is a head's
    tokens one after the other, as the projection's product writes them
    and the recurrence reads them (a change of layout XLA makes none of)."""
    return jnp.swapaxes(x, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _op(qkvz, w_q, w_k, w_v, scale, tile, interpret):
    return _op_fwd(qkvz, w_q, w_k, w_v, scale, tile, interpret)[0]


def _op_fwd(qkvz, w_q, w_k, w_v, scale, tile, interpret):
    out = _forward(_by_head(qkvz), _taps(w_q, w_k, w_v), dl=w_q.shape[1],
                   scale=scale, tile=tile, interpret=interpret)
    return tuple(_by_head(y).astype(qkvz.dtype) for y in out), (
        qkvz, w_q, w_k, w_v)


def _op_bwd(scale, tile, interpret, res, cotangents):
    qkvz, w_q, w_k, w_v = res
    dl = w_q.shape[1]
    dx, dw = _backward(_by_head(qkvz), _taps(w_q, w_k, w_v),
                       *map(_by_head, cotangents), dl=dl, scale=scale,
                       tile=tile, interpret=interpret)
    dw_q, dw_k, dw_v = jnp.split(jnp.swapaxes(dw, 1, 2), (dl, 2 * dl),
                                 axis=1)
    return (_by_head(dx).astype(qkvz.dtype), dw_q.astype(w_q.dtype),
            dw_k.astype(w_k.dtype), dw_v.astype(w_v.dtype))


_op.defvjp(_op_fwd, _op_bwd)


def conv_silu_l2norm(qkvz: jax.Array, w_q: jax.Array, w_k: jax.Array,
                     w_v: jax.Array, scale: float, *,
                     tile: Optional[int] = None,
                     interpret: Optional[bool] = None
                     ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """``(q, k [B, T, Hk, Dl], v, z [B, T, r Hk, Dl])`` of ``qkvz [B, T, Hk,
    (2 + 2 r) Dl]`` under the taps ``w_q, w_k [Hk, Dl, K]``, ``w_v [Hk, r
    Dl, K]``; ``scale`` multiplies the normed q.  ``tile``: tokens a grid
    step, whole sublanes of them (``T`` need not be a whole number of
    tiles).  ``interpret``: None is compiled on a TPU and the Pallas
    interpreter elsewhere."""
    (hk, dl, taps), c = w_q.shape, qkvz.shape[-1]
    share = w_v.shape[1] // dl
    tile = tile or _TILE
    if (w_k.shape != w_q.shape or w_v.shape != (hk, share * dl, taps)
            or qkvz.shape[2] != hk or c != 2 * dl * (1 + share)
            or tile % _HALO or taps - 1 > _HALO):
        raise ValueError(f"qkvz {qkvz.shape}, taps {w_q.shape}, {w_k.shape}, "
                         f"{w_v.shape}, tiles of {tile}")
    return _op(qkvz, w_q, w_k, w_v, float(scale), tile,
               _use_interpret() if interpret is None else interpret)
