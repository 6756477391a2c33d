"""Int8 weight-only quantization + Pallas matmul kernel (TPU serving).

Autoregressive decode is bandwidth-bound: every tick re-reads every
weight matrix from HBM while doing almost no FLOPs (see the decode-tick
anatomy in BASELINE.md).  Weight-only int8 halves that traffic — the
classic serving lever.  The kernel keeps weights **int8 in HBM** and
dequantizes per-tile in VMEM; a naive ``x @ (q * scale)`` in XLA would
materialize the dequantized f32/bf16 matrix in HBM once, after which
every tick re-reads FULL-WIDTH weights and the quantization saves
nothing.

Scheme: symmetric per-output-channel.  For ``w [K, N]``:
``scale[n] = max_k |w[k, n]| / 127``, ``q = round(w / scale)``.  Because
the scale is per OUTPUT column it factors out of the contraction —
``x @ (q * scale) == (x @ q) * scale`` — so the kernel runs one integer
valued matmul per tile and scales the result columns, never
materializing a dequantized weight block.

No counterpart exists in the reference (training-only framework).
Layout/padding conventions follow ``ops/flash_attention.py``; interpret
mode (CPU tests) is selected automatically off-TPU.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from autodist_tpu.ops import pallas_utils, quant_scale

_TILE = pallas_utils.TILE          # MXU lane quantum
_BLOCK_M = 512                     # rows of x per program
_DEFAULT_BLOCK_N = 512


class Quantized(NamedTuple):
    """Weight-only int8 tensor: ``q`` int8 ``[K, N]``, ``scale`` f32
    ``[1, N]`` (per-output-channel symmetric)."""
    q: jax.Array
    scale: jax.Array

    @property
    def shape(self):
        return self.q.shape

    @property
    def nbytes(self) -> int:
        return self.q.size + self.scale.size * 4


def quantize_weight(w: jax.Array) -> Quantized:
    """Symmetric per-output-channel int8 quantization of a 2-D weight.

    ``w``: [K, N] (contraction dim first — transpose embedding tables to
    [D, V] so the per-channel scale lands on the vocab axis)."""
    if w.ndim != 2:
        raise ValueError(f"quantize_weight expects a 2-D matrix, got "
                         f"shape {w.shape}")
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)       # [1, N]
    # Shared scale rule (ops/quant_scale.py): per-channel amax/127 with
    # all-zero columns pinned at the identity scale.
    scale = quant_scale.channel_scale(amax, 127.0)
    q = quant_scale.quantize_values(w / scale, 127.0, jnp.int8,
                                    rounded=True)
    return Quantized(q=q, scale=scale)


_use_interpret = pallas_utils.use_interpret


def _kernel(x_ref, q_ref, s_ref, o_ref):
    """One (M-block, N-block) program: dequant-free int8 matmul + column
    scaling.

    Refs: x [bm, K]; q [K, bn] int8; s [1, bn] f32; o [bm, bn].
    ``q.astype(x.dtype)`` is exact (|q| <= 127 fits bf16's 8-bit
    mantissa); the f32 accumulator keeps the integer dot exact too.
    """
    x = x_ref[...]
    w = q_ref[...].astype(x.dtype)
    acc = jnp.dot(x, w, preferred_element_type=jnp.float32)   # [bm, bn]
    o_ref[...] = (acc * s_ref[...]).astype(o_ref.dtype)


_pad_to = pallas_utils.pad_to


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _int8_matmul_2d(x, q, scale, block_n: int, interpret: bool):
    m, k = x.shape
    kq, n = q.shape
    bn = min(block_n, _pad_to(n, _TILE))
    # M is tiled too: a prefill-sized x ([8192, 768] bf16 is 12 MB, twice
    # that double-buffered) does not fit the 16 MB scoped VMEM whole.
    bm = min(_BLOCK_M, m if interpret else _pad_to(max(m, 8), 8))
    mp = _pad_to(m, bm)
    kp = k if interpret else _pad_to(k, _TILE)
    np_ = _pad_to(n, bn)
    xp = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    qp = jnp.pad(q, ((0, kp - k), (0, np_ - n)))
    sp = jnp.pad(scale, ((0, 0), (0, np_ - n)))
    # N is the inner grid axis: the x block stays resident while the
    # (smaller) weight blocks stream past it.
    out = pl.pallas_call(
        _kernel,
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((kp, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        interpret=interpret,
    )(xp, qp, sp)
    return out[:m, :n]


def int8_matmul(x: jax.Array, w: Quantized, *,
                block_n: int = _DEFAULT_BLOCK_N,
                interpret: Optional[bool] = None) -> jax.Array:
    """``x @ dequant(w)`` with int8 weights resident in HBM.

    ``x``: [..., K] (leading dims flattened for the kernel); returns
    ``[..., N]`` in ``x.dtype``.
    """
    if interpret is None:
        interpret = _use_interpret()
    k = x.shape[-1]
    if w.q.shape[0] != k:
        raise ValueError(f"contraction mismatch: x[..., {k}] @ "
                         f"q{tuple(w.q.shape)}")
    lead = x.shape[:-1]
    x2 = x.reshape((-1, k))
    out = _int8_matmul_2d(x2, w.q, w.scale, int(block_n), bool(interpret))
    return out.reshape(lead + (w.q.shape[1],))
