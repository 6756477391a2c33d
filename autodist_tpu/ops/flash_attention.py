"""Pallas TPU flash attention (forward kernel + ONE fused backward kernel).

The hot op of every transformer in the model zoo.  Dense attention
(``models/transformer.py:dense_attention``) materializes the [B, H, T, T]
score matrix in HBM; this kernel keeps scores in VMEM tiles and streams K/V
blocks through the MXU with an online softmax, so HBM traffic is linear in
sequence length (Dao et al. 2022, "FlashAttention"; TPU formulation per the
Pallas guide's blockwise/online-softmax pattern).

No counterpart exists in the reference — it has no attention kernels at all
(its BERT example leans on stock TF ops, ``examples/benchmark/bert.py``).
This is TPU-native new scope that the long-context machinery
(``autodist_tpu/parallel/ring_attention.py``) composes with: ring attention
shards the sequence *across* chips; this kernel is the fast *within-chip*
block computation.

Layout convention matches the pluggable ``attn_fn`` protocol: q/k/v are
``[batch, seq, heads, head_dim]`` (q and k one width, v and the output
possibly another); internally the kernel runs per (batch,
head) on ``[seq, head_dim]`` blocks, and q, k, v, o and the gradients cross
the custom call's boundary in the caller's type.

What the kernels do, and what was measured (PR 25, one TPU v5 lite chip,
jax 0.9.0; each call timed alone, eight chained in one program; the table
is ``PERF.md`` section 5):

* **Transposed tiles.**  A score tile is ``[bk, bq]``: keys down the
  sublanes, queries along the lanes.  Everything per query (running max and
  sum, lse, Δ) is then a lane-dense row ``[1, bq]`` whose broadcast is
  free, and every accumulator is ``[D, ·]`` with all 128 lanes full where
  ``[·, 64]`` fills half.  Every product's result is then a block wide
  (512 columns), never D wide, which is what keeps the chip's MXUs busy
  at D = 64: written the other way round (dV = Pᵀ·dO as ``[bk, 64]``) the
  fused backward took 0.542 ms for ``[4, 16, 1024, 64]`` where it takes
  0.478 ms, and a forward with ``[bq, bk]`` tiles 0.375 ms for 0.293.
* **One backward call** that forms S, P, dP and dS ONCE per tile and
  accumulates dV, dK and dQ from them (7 products and 2 exponential passes
  a layer with the forward; the dQ and dK/dV calls it replaces took 9 and
  3).  Forward 0.340 -> 0.293 ms, backward 0.354 + 0.563 -> 0.478 ms at
  that shape; 0.746 -> 0.655 and 0.812 + 1.241 -> 1.000 ms at
  ``[2, 8, 4096, 64]``.
* **Products at the stated precision.**  The operands of every product are
  rounded to bfloat16 right before it when the kernel is compiled (one MXU
  pass, float32 sums: XLA's default for every other product of a step, and
  measured to be what Mosaic already did with float32 operands: same
  errors to three digits, same times); statistics, exponentials and
  accumulators stay float32.  Under the interpreter, or when jax is asked
  for ``highest`` products, the operands stay float32 and the products
  ask for ``HIGHEST`` (2.5-4 times slower on the chip, error 1e-7).
* **Masks only where needed.**  Tiles wholly below the diagonal and inside
  ``kv_len`` skip the iota / compare / select.
* **Grouped heads and a selection of keys** (PR 32).  ``H // Hkv`` query
  heads in a row read one key/value head through the index map, with no
  copy; the backward writes each query head's dK and dV and the group's
  are summed outside the kernel.  A learned top-k selection comes in as
  ONE BIT a (query, key) pair, packed into int32 words with keys down and
  queries along (``pack_selection``), and masks every tile of the q
  blocks that reach past row ``select_from``: every causal tile is still
  computed.  Callers without either trace the kernels they always did.
* **A window** (PR 36).  With a static ``window`` a query attends to its
  own position and the ``window - 1`` before it.  The forward's loop over
  key blocks starts at the block the window's trailing edge crosses and
  the fused backward's loop over query blocks ends there: tiles wholly
  behind the window are never visited, and only the tile that edge
  crosses and the diagonal tile pay for a mask.  At 16,384 tokens, tiles
  of 512 and a window of 4,096 that is 252 tiles a head of the causal
  triangle's 528.  ``window=None`` traces the kernels it always did.
* **Block diffusion** (PR 47).  With a static ``block_diffusion=(B, L)``
  the rows are a sequence of ``L`` tokens and, where there are ``2 L`` of
  them, its noised copy behind it, and the mask is NOT causal: a clean
  row attends to the clean rows of its own block of ``B`` and of every
  block before it, a noised row to the clean rows of the blocks before
  its twin's and to the noised rows of its own block, both ways
  (:func:`block_diffusion_mask`).  The tiles divide ``L``, so each lies
  in one quarter of the square; forward and fused backward visit, for a
  clean q block, the clean key blocks up to its own, and for a noised
  one, the clean key blocks up to its twin's and the noised blocks on its
  diagonal.  Only the tiles the rule's edge crosses pay for a mask, a
  compare of ``index // B`` a row and a column and no longer ``key <=
  query``: at ``L`` = 8,192, ``B`` = 4 and tiles of 512 that is 288 tiles
  a head (32 of them masked) of the 528 a causal pass over the 16,384
  rows visits.  ``block_diffusion=None`` traces the kernels it always did.

Interpret mode (CPU tests) is selected automatically off-TPU.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from autodist_tpu.const import MESH_AXIS_DATA, MESH_AXIS_MODEL
from autodist_tpu.ops import pallas_utils
from autodist_tpu.utils import logging

_NEG_INF = -1e30  # finite -inf: keeps exp()/max() NaN-free (masked rows)
#: The forward kernel's output and row statistics (kernel layout:
#: ``o [B,H,T,Dv]``, ``lse [B,H,T,1]`` float32) carry these names, so a
#: ``jax.checkpoint`` policy can keep them (``save_only_these_names``):
#: the backward is then handed what the first forward made and the kernel
#: is not run again.  Where no policy asks for a name, a tag is an
#: identity.
RESIDUAL_NAMES = ("flash_attention/o", "flash_attention/lse")
# Tiling policy lives in ops/pallas_utils.py (shared by every Pallas
# kernel in the repo); these aliases keep this module's historical
# private names importable (tests pin the padding policy through them).
_TILE = pallas_utils.TILE
_pick_block = pallas_utils.pick_block
_pad_len = pallas_utils.pad_len
_use_interpret = pallas_utils.use_interpret
# Default q/k block edge, for every caller and both kernels.  Measured in
# PR 25 on one TPU v5 lite chip (ms a call, causal, forward / backward):
#   [4,16,1024,64] f32: 512x512 0.293/0.478, 256x256 0.467/0.623,
#     512 queries x 256 keys 0.360/0.531, 1024x1024 0.303/0.482
#   [8,12,2048,64] bf16: 512x512 1.178/1.904, 256x256 2.123/2.608,
#     1024x1024 1.176/1.983
#   [2,8,4096,64] f32: 512x512 0.655/1.000, 256x256 1.224/1.455,
#     1024 x 512 0.626/1.023
# Smaller tiles compute less of the causal square (62.5 % at 256 against
# 75 % at 512 of T = 1024) and still lose: a product narrower than 512
# columns leaves MXUs idle.  _pick_block degrades gracefully for sequences
# 512 doesn't divide.
_DEFAULT_BLOCK = 512


_VMEM_TILES = 8 << 20      # room for one tile's temporaries (512 x 512 f32)


def _compiler_params(t: int, block: int, resident, blocked, widths,
                     extra: int = 0):
    """The last grid axis sequential (scratch filled at its first step is
    read at the others), and the scoped-VMEM limit raised when what the
    kernel holds would not fit the default: the ``resident`` [T, D]
    buffers (they stay over that axis), the ``blocked`` [block, D] ones,
    and a tile's temporaries (the [block, block] ones, and some four
    float32 [D, block] values of each of the ``widths``: operands,
    accumulators).  Buffers are ``(width, bytes a number)`` as
    :func:`_row_bytes` takes them; q and k are ``Dk`` wide, v and o
    ``Dv``.  The backward holds 2.5 KB (bfloat16 in, D = 128) to 4 KB
    (float32 in) a row: T = 4096 fits the 16 MiB default, T = 16384 at
    D = 128 asks for 48-72 MiB, and the v5e's VMEM ends between T = 16384
    (float32 in) and 32768 (bfloat16 in); the forward holds about half of
    that.  At ``Dk`` 192, ``Dv`` 128 and float32 in, the backward holds
    7 KB a row, 40 MiB in all at T = 4096 (what Mosaic asked for when
    the limit stood at 38).  ``extra``: bytes of what else is held (a
    selection's words)."""
    need = (extra + _VMEM_TILES + t * _row_bytes(*resident) + block * (
        _row_bytes(*blocked) + _row_bytes(*[(d, 4) for d in widths] * 4)))
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=pallas_utils.vmem_limit(need))


def _row_bytes(*buffers) -> int:
    """Bytes one row of ``(width, bytes a number)`` buffers holds: an
    input or output block is listed twice (it is double-buffered); a
    width pads to whole 128-lane tiles."""
    return sum(-(-d // _TILE) * _TILE * size for d, size in buffers)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b


def _block(i, size: int, count: int):
    """Rows (or lanes) of block ``i`` of ``count``.  One block starts at a
    static 0: a short sequence is ONE block of any multiple of 8, and a
    dynamic lane index has to be provably a multiple of 128."""
    return pl.ds(0 if count == 1 else pl.multiple_of(i * size, size), size)


def _dot(a, b, dims, operand):
    """One MXU product with float32 sums.  ``operand`` is the type both
    sides are rounded to right before it: bfloat16 is ONE pass (what XLA's
    default precision does to every other product of a step); float32
    asks for ``HIGHEST``, so the request is honoured on the chip rather
    than left to Mosaic's default for float32 operands."""
    precision = lax.Precision.HIGHEST if operand == jnp.float32 else None
    return lax.dot_general(a.astype(operand), b.astype(operand), dims,
                           precision=precision,
                           preferred_element_type=jnp.float32)


_WORD = 32    # keys a word of a packed selection holds


def pack_selection(selected: jax.Array, *,
                   block_k: int = _DEFAULT_BLOCK) -> jax.Array:
    """``selected [..., Tq, Tk]`` bool (query ``t`` attends to key ``s``)
    to the words the kernels read: ``[..., Tk // 32, Tq]`` int32, keys
    down, queries along.  Inside a block of ``bk`` keys (the kernels'
    own, ``pick_block(Tk, block_k)``) key ``r`` is bit ``r // W`` of word
    ``r % W``, ``W = bk // 32``: a tile's mask is then its ``[W, bq]``
    words shifted 32 ways and stacked, with no lane or sublane moved."""
    tq, tk = selected.shape[-2:]
    bk = _pick_block(tk, block_k)
    if bk % _WORD:
        raise ValueError(f"a selection needs key blocks of a multiple of "
                         f"{_WORD}; {tk} keys give blocks of {bk}")
    words = bk // _WORD
    bits = selected.reshape(selected.shape[:-1] + (tk // bk, _WORD, words))
    packed = jnp.sum(bits.astype(jnp.uint32)
                     << jnp.arange(_WORD, dtype=jnp.uint32)[:, None],
                     axis=-2, dtype=jnp.uint32)
    packed = jax.lax.bitcast_convert_type(packed, jnp.int32)
    return jnp.swapaxes(packed.reshape(selected.shape[:-1] + (tk // _WORD,)),
                        -1, -2)


def unpack_selection(words: jax.Array, *,
                     block_k: int = _DEFAULT_BLOCK) -> jax.Array:
    """:func:`pack_selection` undone: ``[..., Tk // 32, Tq]`` words to
    ``[..., Tq, Tk]`` bool (for an attention that is not the kernel)."""
    tk, tq = words.shape[-2] * _WORD, words.shape[-1]
    per_block = _pick_block(tk, block_k) // _WORD
    w = jnp.swapaxes(words, -1, -2).reshape(
        words.shape[:-2] + (tq, -1, 1, per_block))
    bits = (w >> jnp.arange(_WORD, dtype=jnp.int32)[:, None]) & 1
    return bits.reshape(words.shape[:-2] + (tq, tk)) != 0


def _bd_blocks(t: int, block_q: int, block_k: int, bd) -> tuple:
    """The (q, k) block edges of ``t`` rows: divisors of ``t``, and under
    ``bd = (B, L)`` of ``L``, so that a tile lies in one quarter of the
    square of clean and noised rows."""
    whole = t if bd is None else bd[1]
    return _pick_block(whole, block_q), _pick_block(whole, block_k)


def _bd_key_blocks(q0, bq: int, bk: int, bd, *, div=lax.div, where=jnp.where):
    """The key blocks a q block that starts at row ``q0`` visits under
    ``bd = (B, L)``: ``(clear, upper, first, last)``: clean key blocks
    ``[0, clear)`` hold only keys every row of the block attends to,
    ``[clear, upper)`` are crossed by the rule's edge, and the noised key
    blocks ``[first, last)`` hold the block's own diagonal (none for a
    clean q block).  Scalars, traced in the kernel and plain integers in
    :func:`pairs_computed` (``div``, ``where``)."""
    b, l = bd
    noised = q0 >= l
    i0 = q0 - where(noised, l, 0)
    # the starts of the blocks of B that hold its first and its last row
    lo, hi = div(i0, b) * b, div(i0 + bq - 1, b) * b
    clear = div(where(noised, lo, lo + b), bk)
    upper = div(where(noised, hi, hi + b) + bk - 1, bk)
    first = where(noised, div(l + lo, bk), 0)
    last = where(noised, div(l + hi + b + bk - 1, bk), 0)
    return clear, upper, first, last


def _bd_query_blocks(k0, bk: int, bq: int, bd, num_qb: int) -> tuple:
    """:func:`_bd_key_blocks` read the other way, for the fused backward:
    the q blocks of the key block that starts at row ``k0``, as ``(edge,
    clear, last)`` twice, for the clean q blocks and for the noised ones:
    blocks ``[edge, clear)`` are crossed by the rule's edge and ``[clear,
    last)`` attend to every key of the block.  A noised key block has
    only the noised q blocks on its diagonal.  Traced scalars."""
    b, l = bd
    half = l // bq                      # q blocks of the clean rows
    noised = k0 >= l
    j0 = k0 - jnp.where(noised, l, 0)
    lo = lax.div(j0, b) * b                         # its first key's block
    hi = lax.div(j0 + bk + b - 1, b) * b            # the end of its last's
    clean = (jnp.where(noised, half, lax.div(lo, bq)),
             jnp.where(noised, half, jnp.minimum(
                 lax.div(hi - b + bq - 1, bq), half)), half)
    past = jnp.minimum(lax.div(l + hi + bq - 1, bq), num_qb)
    return clean, (lax.div(l + jnp.where(noised, lo, lo + b), bq), past,
                   jnp.where(noised, past, num_qb))


def pairs_computed(t: int, *, block_q: int = _DEFAULT_BLOCK,
                   block_k: int = _DEFAULT_BLOCK,
                   window: Optional[int] = None,
                   block_diffusion: Optional[Tuple[int, int]] = None) -> int:
    """(query, key) pairs whose score ONE causal forward call forms for
    one head: every tile at or below the diagonal, whole; under a
    ``window``, from the tile its trailing edge crosses on (the kernel's
    own first key block).  Under ``block_diffusion=(B, L)`` (not causal;
    ``t`` is ``L`` or ``2 L``) the tiles :func:`_bd_key_blocks` names."""
    if block_diffusion is not None:
        bq, bk = _bd_blocks(t, block_q, block_k, block_diffusion)
        tiles = 0
        for q0 in range(0, t, bq):
            clear, upper, first, last = _bd_key_blocks(
                q0, bq, bk, block_diffusion, div=lambda a, b: a // b,
                where=lambda c, a, b: a if c else b)
            tiles += upper + last - first
        return tiles * bq * bk
    bq, bk = _pick_block(t, block_q), _pick_block(t, block_k)
    return sum((min(-(-(q0 + bq) // bk), t // bk)
                - (0 if window is None else max(q0 - window + 1, 0) // bk))
               * bk * bq for q0 in range(0, t, bq))


def block_diffusion_mask(block: int, length: int, rows: int) -> jax.Array:
    """``[rows, rows]`` bool, query down and key along: what
    ``block_diffusion=(block, length)`` lets a row attend to.  Rows ``0 ..
    L - 1`` are a sequence (clean), rows ``L .. 2 L - 1`` its noised copy
    (``rows`` is ``L`` or ``2 L``).  Clean to clean: the key's block is at
    or before the query's; noised to clean: before its twin's; noised to
    noised: its own block, both ways; clean to noised: never.  For an
    attention that is not the kernel."""
    r = jnp.arange(rows)
    noised, blk = r >= length, (r % length) // block
    q_noised, k_noised = noised[:, None], noised[None, :]
    q_blk, k_blk = blk[:, None], blk[None, :]
    return jnp.where(k_noised, q_noised & (k_blk == q_blk),
                     jnp.where(q_noised, k_blk < q_blk, k_blk <= q_blk))


def _div(x, b: int):
    """``x // b`` of a non-negative int32 vector inside a kernel: a shift
    where ``b`` is a power of two."""
    if b & (b - 1) == 0:
        return x >> (b.bit_length() - 1)
    return lax.div(x, jnp.int32(b))


def _bd_codes(index, bd):
    """A row index (query or key) as the block-diffusion rule reads it:
    ``(code, upto, twin)``.  A KEY is its ``code``: its block's number if
    clean, that plus ``L`` (past every clean code) if noised.  A QUERY
    attends to the keys whose code is at most ``upto`` (its own block if
    clean, the one before its twin's if noised) or equal to ``twin`` (a
    noised query's own block among the noised keys; no key's for a clean
    one)."""
    b, l = bd
    noised = index >= l
    blk = _div(jnp.where(noised, index - l, index), b)
    return (jnp.where(noised, blk + l, blk), jnp.where(noised, blk - 1, blk),
            jnp.where(noised, blk + l, -1))


def _unpack(words):
    """``[W, bq]`` int32 words of one key block to its mask ``[32 W, bq]``
    (:func:`pack_selection`)."""
    return jnp.concatenate([(words >> b) & 1 for b in range(_WORD)],
                           axis=0) != 0


def _fwd_kernel(*refs, causal: bool, block_k: int, scale: float,
                kv_len: int, operand, select_from: Optional[int] = None,
                window: Optional[int] = None,
                bd: Optional[Tuple[int, int]] = None):
    """One (batch, head, q-block) program: stream K/V blocks, online softmax.

    Tiles are TRANSPOSED, ``[bk, bq]`` (keys down the sublanes, queries
    along the lanes): the running max and sum are lane-dense rows
    ``[1, bq]`` (a handful of registers, reduced over sublanes on the VPU)
    and Oᵀ ``[D, bq]`` fills every lane where O ``[bq, 64]`` fills half.

    Refs: q [1,1,bq,Dk], o [1,1,bq,Dv]; k [1,1,T,Dk], v [1,1,T,Dv]
    (resident over the q-block axis, which is sequential); lse
    [1,1,1,bq].  Scratch, filled at the first q block of a (batch, head):
    the product operands K [T,Dk] and Vᵀ [Dv,T].
    ``kv_len`` < T means the tail is alignment padding — masked out.
    Only tiles the diagonal or the padding edge crosses pay for the mask.

    With ``select_from`` (the ``topk`` of a learned selection) a further
    ref follows v: sel [1,T/32,bq], the packed words of
    :func:`pack_selection` for this block's queries.  A q block that ends
    at or before row ``select_from`` attends to every earlier key and
    takes the tiles above; any other masks EVERY tile up to the diagonal
    by its words (a selection lies inside the causal triangle).

    With ``window`` (causal) query ``t`` attends to keys ``t - window <
    s <= t``: K blocks wholly behind the window of the block's first
    query are never visited, and those its trailing edge crosses are
    masked by it.

    With ``bd = (B, L)`` (not causal, no padding) the K blocks are those
    :func:`_bd_key_blocks` names, and the ones the rule's edge crosses are
    masked by :func:`_bd_codes`.
    """
    if select_from is None:
        q_ref, k_ref, v_ref, o_ref, lse_ref, ks_ref, vt_ref = refs
    else:
        q_ref, k_ref, v_ref, sel_ref, o_ref, lse_ref, ks_ref, vt_ref = refs
    bq, dv = q_ref.shape[2], v_ref.shape[3]
    t_k = k_ref.shape[2]
    padded = kv_len < t_k
    num_kb = t_k // block_k
    qi = pl.program_id(2)
    q0 = qi * bq

    @pl.when(qi == 0)
    def _():
        def fill(kb, _):
            rows = _block(kb, block_k, num_kb)
            ks_ref[rows, :] = k_ref[0, 0, rows, :].astype(operand)
            vt_ref[:, rows] = v_ref[0, 0, rows, :].astype(
                jnp.float32).T.astype(operand)
            return 0
        lax.fori_loop(0, num_kb, fill, 0)

    q = (q_ref[0, 0].astype(jnp.float32) * scale).astype(operand)  # [bq,Dk]
    # k_pos <= q_pos  <=>  row - col <= q0 - k0
    row = lax.broadcasted_iota(jnp.int32, (block_k, bq), 0)
    row_minus_col = row - lax.broadcasted_iota(jnp.int32, (block_k, bq), 1)
    # a window shorter than two tiles can cross a diagonal tile as well
    near = window is not None and window < bq + block_k
    if bd is not None:
        _, upto, twin = _bd_codes(
            q0 + lax.broadcasted_iota(jnp.int32, (1, bq), 1), bd)   # [1,bq]
        key = lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)

    def tile(masked, kb, carry):
        o_t, l, m = carry
        k0, rows = kb * block_k, _block(kb, block_k, num_kb)
        s_t = _dot(ks_ref[rows, :], q, _NT, operand)            # [bk,bq]
        if masked == "rule":
            code = _bd_codes(k0 + key, bd)[0]                   # [bk,1]
            s_t = jnp.where((code <= upto) | (code == twin), s_t, _NEG_INF)
        elif masked == "selected":
            s_t = jnp.where(_unpack(sel_ref[0, _block(
                kb, block_k // _WORD, num_kb), :]), s_t, _NEG_INF)
        elif masked == "edge":
            s_t = jnp.where(row_minus_col > q0 - k0 - window, s_t, _NEG_INF)
        elif masked:
            mask = row_minus_col <= q0 - k0 if causal else None
            if near:
                mask &= row_minus_col > q0 - k0 - window
            if padded:
                live = row < kv_len - k0
                mask = live if mask is None else mask & live
            s_t = jnp.where(mask, s_t, _NEG_INF)
        m_new = jnp.maximum(m, s_t.max(axis=0, keepdims=True))  # [1,bq]
        p_t = jnp.exp(s_t - m_new)                              # [bk,bq]
        corr = jnp.exp(m - m_new)                               # [1,bq]
        l_new = l * corr + p_t.sum(axis=0, keepdims=True)
        o_new = o_t * corr + _dot(vt_ref[:, rows], p_t, _NN, operand)  # [Dv,bq]
        return o_new, l_new, m_new

    init = (jnp.zeros((dv, bq), jnp.float32), jnp.zeros((1, bq), jnp.float32),
            jnp.full((1, bq), _NEG_INF, jnp.float32))
    # K blocks [0, clear) lie wholly below the diagonal and inside kv_len:
    # no mask.  Blocks [clear, upper) cross one of the two; the rest are
    # wholly above the diagonal and contribute nothing.
    clear = upper = num_kb
    if causal:
        clear = lax.div(q0 + 1, block_k)
        upper = jnp.minimum(lax.div(q0 + bq + block_k - 1, block_k), num_kb)
    if padded:
        clear = jnp.minimum(clear, kv_len // block_k)
    first = 0
    if select_from is not None:
        first = jnp.where(q0 + bq > select_from, upper, 0)
        init = lax.fori_loop(0, first, functools.partial(tile, "selected"),
                             init)
        clear = jnp.maximum(clear, first)
    if window is not None:
        # K blocks before ``first`` lie wholly behind the window; blocks
        # [first, inside) are crossed by its trailing edge
        first = lax.div(jnp.maximum(q0 - window + 1, 0), block_k)
        inside = jnp.minimum(lax.div(
            jnp.maximum(q0 + bq - window, 0) + block_k - 1, block_k), clear)
        init = lax.fori_loop(first, inside, functools.partial(tile, "edge"),
                             init)
        first = inside
    if bd is not None:
        clear, upper, twins, twins_end = _bd_key_blocks(q0, bq, block_k, bd)
    carry = lax.fori_loop(first, clear, functools.partial(tile, False), init)
    if bd is not None:
        for blocks in ((clear, upper), (twins, twins_end)):
            carry = lax.fori_loop(*blocks, functools.partial(tile, "rule"),
                                  carry)
    if causal or padded:
        carry = lax.fori_loop(clear, upper, functools.partial(tile, True),
                              carry)
    o_t, l, m = carry
    l = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (o_t / l).T.astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)


def _kv_head(group: int):
    """The key/value head a query head reads: itself, or one of every
    ``group`` in a row."""
    return (lambda hi: hi) if group == 1 else (lambda hi: hi // group)


def _fwd(q, k, v, causal, block_q, block_k, interpret, kv_len,
         operand=jnp.float32, sel=None, select_from=None, window=None,
         bd=None):
    """q: [B, H, T, Dk], k: [B, Hkv, T, Dk], v: [B, Hkv, T, Dv] → (o
    [B,H,T,Dv], lse [B,H,T,1]); the scale is 1/√Dk.  ``H // Hkv`` query
    heads in a row read one key/value head: the index map repeats, and
    nothing is copied.  ``sel``: ``pack_selection``'s words [B, T/32,
    T]."""
    b, h, t, dk = q.shape
    dv = v.shape[3]
    kv_head = _kv_head(h // k.shape[1])
    bq, bk = _bd_blocks(t, block_q, block_k, bd)
    scale = 1.0 / (dk ** 0.5)
    kernel = functools.partial(_fwd_kernel, causal=causal, block_k=bk,
                               scale=scale, kv_len=kv_len, operand=operand,
                               select_from=select_from, window=window,
                               bd=bd)
    selection = [] if sel is None else [pl.BlockSpec(
        (1, t // _WORD, bq), lambda bi, hi, qi: (bi, 0, qi))]

    in_size, op_size = q.dtype.itemsize, jnp.dtype(operand).itemsize
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, h, t // bq),
        in_specs=[pl.BlockSpec((1, 1, bq, dk),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
                  pl.BlockSpec((1, 1, t, dk),
                               lambda bi, hi, qi: (bi, kv_head(hi), 0, 0)),
                  pl.BlockSpec((1, 1, t, dv),
                               lambda bi, hi, qi: (bi, kv_head(hi), 0, 0))]
        + selection,
        out_specs=[
            pl.BlockSpec((1, 1, bq, dv), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda bi, hi, qi: (bi, hi, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, t), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((t, dk), operand),
                        pltpu.VMEM((dv, t), operand)],
        # k, v in (twice each), K and Vᵀ as operands; q in, o out (twice)
        compiler_params=_compiler_params(
            t, bq, [(dk, in_size)] * 2 + [(dv, in_size)] * 2
            + [(dk, op_size), (dv, op_size)],
            [(dk, in_size)] * 2 + [(dv, in_size)] * 2, (dk, dv),
            extra=len(selection) * 2 * (t // _WORD) * bq * 4),
        interpret=interpret,
    )(q, k, v, *([] if sel is None else [sel]))
    return o, lse.reshape(b, h, t, 1)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_kernel(*refs, causal: bool, block_q: int, scale: float,
                kv_len: int, operand, select_from: Optional[int] = None,
                window: Optional[int] = None,
                bd: Optional[Tuple[int, int]] = None):
    """dQ, dK and dV in one call: each score tile is formed ONCE.

    One (batch, head, k-block) program; the k-block axis is sequential.
    With tiles transposed (``[bk, bq]``) and Q, dO held transposed:

        Sᵀ = K·(Qᵀ·scale)   Pᵀ = exp(Sᵀ − lse)   dPᵀ = V·dOᵀ
        dSᵀ = Pᵀ∘(dPᵀ − Δ)
        dVᵀ += dOᵀ·P   dKᵀ += (Qᵀ·scale)·dS   dQᵀ += Kᵀ·dSᵀ

    Refs: q/dq [1,1,T,Dk] and do [1,1,T,Dv] (resident over the k-block
    axis); k/dk [1,1,bk,Dk], v/dv [1,1,bk,Dv]; lse/Δ [1,1,1,T].  Scratch,
    filled at the first k block of a (batch, head): the product operands
    Qᵀ·scale [Dk,T] and dOᵀ [Dv,T] (rounded and transposed once, not once
    a tile) and the float32 dQᵀ [Dk,T], scaled, transposed and written
    back at the last k block.

    With ``select_from`` a further input follows Δ: sel [1,bk/32,T], this
    key block's words for every query (:func:`_fwd_kernel` says which q
    blocks read them).

    With ``window`` the q blocks of a key block end where the window of
    its last key does, ``(k0 + bk + window - 2) // block_q``; those the
    window's trailing edge crosses are masked by it.

    With ``bd = (B, L)`` the q blocks of a key block are the forward's
    tiles read the other way (:func:`_bd_query_blocks`).
    """
    if select_from is None:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, qst_ref, dot_ref, dqt_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sel_ref,
         dq_ref, dk_ref, dv_ref, qst_ref, dot_ref, dqt_ref) = refs
    bk = k_ref.shape[2]
    t_q = q_ref.shape[2]
    padded = kv_len < t_q
    num_qb = t_q // block_q
    ki = pl.program_id(2)
    k0 = ki * bk

    @pl.when(ki == 0)
    def _():
        def fill(qb, _):
            rows = _block(qb, block_q, num_qb)
            qs = q_ref[0, 0, rows, :].astype(jnp.float32) * scale
            qst_ref[:, rows] = qs.T.astype(operand)
            dot_ref[:, rows] = do_ref[0, 0, rows, :].astype(
                jnp.float32).T.astype(operand)
            return 0
        lax.fori_loop(0, num_qb, fill, 0)
        dqt_ref[...] = jnp.zeros_like(dqt_ref)

    k = k_ref[0, 0].astype(operand)                          # [bk, Dk]
    v = v_ref[0, 0].astype(operand)                          # [bk, Dv]
    k_t = k_ref[0, 0].astype(jnp.float32).T.astype(operand)  # [Dk, bk]
    # k_pos <= q_pos  <=>  row - col <= q0 - k0
    row = lax.broadcasted_iota(jnp.int32, (bk, block_q), 0)
    row_minus_col = row - lax.broadcasted_iota(jnp.int32, (bk, block_q), 1)
    live = row < kv_len - k0 if padded else None
    # a window shorter than two tiles can cross a diagonal tile as well,
    # and every q block of a key block that holds padding takes that mask
    near = window is not None and (window < bk + block_q or padded)
    if bd is not None:
        code = _bd_codes(
            k0 + lax.broadcasted_iota(jnp.int32, (bk, 1), 0), bd)[0]  # [bk,1]
        query = lax.broadcasted_iota(jnp.int32, (1, block_q), 1)

    def tile(masked, qb, carry):
        dk_t, dv_t = carry
        cols = _block(qb, block_q, num_qb)
        qs_t = qst_ref[:, cols]                             # [Dk, bq]
        do_t = dot_ref[:, cols]                             # [Dv, bq]
        s_t = _dot(k, qs_t, _NN, operand)                   # [bk, bq]
        if masked == "rule":
            _, upto, twin = _bd_codes(qb * block_q + query, bd)  # [1,bq]
            s_t = jnp.where((code <= upto) | (code == twin), s_t, _NEG_INF)
        elif masked == "selected":
            s_t = jnp.where(_unpack(sel_ref[0, :, cols]), s_t, _NEG_INF)
        elif masked == "edge":
            s_t = jnp.where(row_minus_col > qb * block_q - k0 - window, s_t,
                            _NEG_INF)
        elif masked:
            mask = row_minus_col <= qb * block_q - k0 if causal else None
            if near:
                mask &= row_minus_col > qb * block_q - k0 - window
            if padded:
                mask = live if mask is None else mask & live
            s_t = jnp.where(mask, s_t, _NEG_INF)
        p_t = jnp.exp(s_t - lse_ref[0, 0, :, cols])         # recomputed probs
        dp_t = _dot(v, do_t, _NN, operand)
        ds_t = (p_t * (dp_t - delta_ref[0, 0, :, cols])).astype(operand)
        dv_t = dv_t + _dot(do_t, p_t, _NT, operand)         # [Dv, bk]
        dk_t = dk_t + _dot(qs_t, ds_t, _NT, operand)        # [Dk, bk]
        dqt_ref[:, cols] += _dot(k_t, ds_t, _NN, operand)   # [Dk, bq]
        return dk_t, dv_t

    # q blocks [lower, clear) cross the diagonal (or, every one of them,
    # the padding edge); blocks [clear, num_qb) lie wholly below it: no
    # mask.  Blocks before ``lower`` are wholly above: nothing to do.
    lower, clear = 0, 0
    if causal:
        lower = lax.div(k0, block_q)
        clear = jnp.minimum(lax.div(k0 + bk + block_q - 2, block_q), num_qb)
    if padded:
        clear = jnp.where(k0 + bk > kv_len, num_qb, clear)
    carry = (jnp.zeros(k_t.shape, jnp.float32),
             jnp.zeros((v.shape[1], bk), jnp.float32))
    last, masked_to = num_qb, clear
    if select_from is not None:
        # q blocks from ``last`` on hold a row that selects
        last = min(select_from // block_q, num_qb)
        masked_to = jnp.minimum(clear, last)
        carry = lax.fori_loop(jnp.maximum(lower, last), num_qb,
                              functools.partial(tile, "selected"), carry)
    if window is not None:
        # q blocks from ``last`` on lie wholly past the window of this
        # block's last key; blocks [inside, last) are crossed by its edge
        last = jnp.minimum(lax.div(k0 + bk + window - 2, block_q) + 1,
                           num_qb)
        masked_to = jnp.minimum(clear, last)
        inside = jnp.minimum(jnp.maximum(lax.div(k0 + window, block_q),
                                         clear), last)
        carry = lax.fori_loop(inside, last, functools.partial(tile, "edge"),
                              carry)
        last = inside
    if causal or padded:
        carry = lax.fori_loop(lower, masked_to,
                              functools.partial(tile, True), carry)
    if bd is not None:
        # the clean q blocks the edge crosses and the same of the noised
        # ones, then the noised ones past the edge; the clean ones past it,
        # ``[clear, last)``, are the loop's below
        (edge, clear, last), noised = _bd_query_blocks(k0, bk, block_q, bd,
                                                       num_qb)
        carry = lax.fori_loop(edge, clear, functools.partial(tile, "rule"),
                              carry)
        carry = lax.fori_loop(noised[0], noised[1],
                              functools.partial(tile, "rule"), carry)
        carry = lax.fori_loop(noised[1], noised[2],
                              functools.partial(tile, False), carry)
    dk_t, dv_t = lax.fori_loop(clear, last, functools.partial(tile, False),
                               carry)
    # q was pre-scaled, so dSᵀQ already carries the 1/√d factor.
    dk_ref[0, 0] = dk_t.T.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv_t.T.astype(dv_ref.dtype)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        def write(qb, _):
            rows = _block(qb, block_q, num_qb)
            dq_ref[0, 0, rows, :] = (dqt_ref[:, rows].T * scale).astype(
                dq_ref.dtype)
            return 0
        lax.fori_loop(0, num_qb, write, 0)


def _bwd(q, k, v, o, lse, do, causal, block_q, block_k, interpret, kv_len,
         dlse=None, operand=jnp.float32, sel=None, select_from=None,
         window=None, bd=None):
    """(dq, dk, dv).  Where ``group = H // Hkv`` query heads share a
    key/value head, the kernel writes each query head's dK and dV and
    the group's are summed here: 2 x [B,H,T,D] crosses memory once more,
    and no program waits for another's block."""
    b, h, t, dk = q.shape
    dv = v.shape[3]
    group = h // k.shape[1]
    bq, bk = _bd_blocks(t, block_q, block_k, bd)
    scale = 1.0 / (dk ** 0.5)
    # Δ_i = Σ_d dO_id · O_id — the softmax-normalization gradient term;
    # a cheap elementwise reduce, left to XLA fusion.  An lse cotangent
    # folds in here: dS_ij = P_ij (dP_ij − Δ_i + dlse_i), so passing
    # Δ' = Δ − dlse reuses the kernel unchanged.  Both go in as rows
    # [B,H,1,T]: lane-dense, where [T,1] columns pad every number to 128.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    delta = delta.reshape(b, h, 1, t)
    lse = lse.reshape(b, h, 1, t)

    def kb_spec(d, group=1):
        head = _kv_head(group)
        return pl.BlockSpec((1, 1, bk, d),
                            lambda bi, hi, i: (bi, head(hi), i, 0))

    def full_spec(d):
        return pl.BlockSpec((1, 1, t, d), lambda bi, hi, i: (bi, hi, 0, 0))

    row_spec = pl.BlockSpec((1, 1, 1, t), lambda bi, hi, i: (bi, hi, 0, 0))
    in_size, op_size = q.dtype.itemsize, jnp.dtype(operand).itemsize
    selection = [] if sel is None else [pl.BlockSpec(
        (1, bk // _WORD, t), lambda bi, hi, i: (bi, i, 0))]
    dq, dk_heads, dv_heads = pl.pallas_call(
        functools.partial(_bwd_kernel, causal=causal, block_q=bq,
                          scale=scale, kv_len=kv_len, operand=operand,
                          select_from=select_from, window=window, bd=bd),
        grid=(b, h, t // bk),
        in_specs=[full_spec(dk), kb_spec(dk, group), kb_spec(dv, group),
                  full_spec(dv), row_spec, row_spec] + selection,
        out_specs=[full_spec(dk), kb_spec(dk), kb_spec(dv)],
        out_shape=[jax.ShapeDtypeStruct((b, h, t, dk), q.dtype),
                   jax.ShapeDtypeStruct((b, h, t, dk), k.dtype),
                   jax.ShapeDtypeStruct((b, h, t, dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((dk, t), operand),
                        pltpu.VMEM((dv, t), operand),
                        pltpu.VMEM((dk, t), jnp.float32)],
        # q, do in and dq out (twice each), Qᵀ, dOᵀ as operands, dQᵀ;
        # k, v in and dk, dv out (twice each)
        compiler_params=_compiler_params(
            t, bk, [(dk, in_size)] * 4 + [(dv, in_size)] * 2
            + [(dk, op_size), (dv, op_size), (dk, 4)],
            [(dk, in_size)] * 4 + [(dv, in_size)] * 4, (dk, dv),
            extra=len(selection) * 2 * (bk // _WORD) * t * 4),
        interpret=interpret,
    )(q, k, v, do, lse, delta, *([] if sel is None else [sel]))
    if group == 1:
        return dq, dk_heads, dv_heads
    return (dq,) + tuple(
        g.reshape(b, h // group, group, t, g.shape[-1]).sum(axis=2)
        for g in (dk_heads, dv_heads))


# ---------------------------------------------------------------------------
# public op ([B, T, H, D] layout, custom VJP)
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, block_q, block_k, interpret, kv_len,
           operand=jnp.float32):
    return _fwd(q, k, v, causal, block_q, block_k, interpret, kv_len, operand)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, kv_len, operand):
    o, lse = _fwd(q, k, v, causal, block_q, block_k, interpret, kv_len,
                  operand)
    # tagged BEFORE they part into result and residual: a policy that
    # keeps the result alone would still recompute the residual
    o, lse = map(checkpoint_name, (o, lse), RESIDUAL_NAMES)
    return (o, lse), (q, k, v, o, lse)


def _flash_bwd(causal, block_q, block_k, interpret, kv_len, operand, res,
               cts):
    q, k, v, o, lse = res
    do, dlse = cts
    return _bwd(q, k, v, o, lse, do, causal, block_q, block_k, interpret,
                kv_len, dlse=dlse, operand=operand)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_selected(q, k, v, sel, block_q, block_k, interpret, operand,
                    select_from):
    """:func:`_flash`, causal, where query ``t`` attends only to the keys
    ``sel`` (``pack_selection``'s words, no gradient) marks for it."""
    return _fwd(q, k, v, True, block_q, block_k, interpret, q.shape[2],
                operand, sel, select_from)


def _flash_selected_fwd(q, k, v, sel, block_q, block_k, interpret, operand,
                        select_from):
    o, lse = map(checkpoint_name, _fwd(
        q, k, v, True, block_q, block_k, interpret, q.shape[2], operand, sel,
        select_from), RESIDUAL_NAMES)
    return (o, lse), (q, k, v, sel, o, lse)


def _flash_selected_bwd(block_q, block_k, interpret, operand, select_from,
                        res, cts):
    q, k, v, sel, o, lse = res
    do, dlse = cts
    return _bwd(q, k, v, o, lse, do, True, block_q, block_k, interpret,
                q.shape[2], dlse=dlse, operand=operand, sel=sel,
                select_from=select_from) + (None,)


_flash_selected.defvjp(_flash_selected_fwd, _flash_selected_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_windowed(q, k, v, block_q, block_k, interpret, kv_len, operand,
                    window):
    """:func:`_flash`, causal, where query ``t`` attends to keys ``t -
    window < s <= t``."""
    return _fwd(q, k, v, True, block_q, block_k, interpret, kv_len, operand,
                window=window)


def _flash_windowed_fwd(q, k, v, block_q, block_k, interpret, kv_len,
                        operand, window):
    o, lse = map(checkpoint_name, _fwd(
        q, k, v, True, block_q, block_k, interpret, kv_len, operand,
        window=window), RESIDUAL_NAMES)
    return (o, lse), (q, k, v, o, lse)


def _flash_windowed_bwd(block_q, block_k, interpret, kv_len, operand, window,
                        res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    return _bwd(q, k, v, o, lse, do, True, block_q, block_k, interpret,
                kv_len, dlse=dlse, operand=operand, window=window)


_flash_windowed.defvjp(_flash_windowed_fwd, _flash_windowed_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_block_diffusion(q, k, v, block_q, block_k, interpret, operand, bd):
    """:func:`_flash`, not causal, under the mask of ``bd = (B, L)``
    (:func:`block_diffusion_mask`)."""
    return _fwd(q, k, v, False, block_q, block_k, interpret, q.shape[2],
                operand, bd=bd)


def _flash_block_diffusion_fwd(q, k, v, block_q, block_k, interpret, operand,
                               bd):
    o, lse = map(checkpoint_name, _fwd(
        q, k, v, False, block_q, block_k, interpret, q.shape[2], operand,
        bd=bd), RESIDUAL_NAMES)
    return (o, lse), (q, k, v, o, lse)


def _flash_block_diffusion_bwd(block_q, block_k, interpret, operand, bd, res,
                               cts):
    q, k, v, o, lse = res
    do, dlse = cts
    return _bwd(q, k, v, o, lse, do, False, block_q, block_k, interpret,
                q.shape[2], dlse=dlse, operand=operand, bd=bd)


_flash_block_diffusion.defvjp(_flash_block_diffusion_fwd,
                              _flash_block_diffusion_bwd)


@functools.cache
def _log_operand(name: str, why: str) -> None:
    logging.info("flash attention: product operands %s (%s)", name, why)


def _product_operand(interpret: bool):
    """The type every product's operands are rounded to, from what the
    code can observe: compiled for the TPU, bfloat16 (one MXU pass with
    float32 sums, the default precision of every other product of a
    step) unless jax's ``jax_default_matmul_precision`` asks for
    ``highest`` / ``float32``; under the interpreter, float32.  Logged
    once per process and resolved type."""
    asked = str(jax.config.jax_default_matmul_precision or "default").lower()
    if interpret:
        operand, why = jnp.float32, "interpret mode"
    elif asked in ("highest", "float32"):
        operand, why = jnp.float32, f"jax_default_matmul_precision={asked}"
    else:
        operand, why = jnp.bfloat16, f"compiled, precision {asked}"
    _log_operand(jnp.dtype(operand).name, why)
    return operand


def _pad_and_run(q, k, v, causal, block_q, block_k, interpret,
                 selection=None, select_from=None, window=None,
                 block_diffusion=None):
    """[B,T,H,D] public layout (q, k ``Dk`` wide, v ``Dv``) → padded
    [B,H,T,D] kernel run → sliced (o [B,T,H,Dv], lse [B,H,T])."""
    t = q.shape[1]
    if block_diffusion is not None:
        b, l = map(int, block_diffusion)
        if (causal or selection is not None or window is not None
                or b < 1 or l % b or t not in (l, 2 * l)
                or _pad_len(l, interpret) != l):
            raise ValueError(
                "block diffusion is not causal, comes without a selection "
                "or a window, and takes L or 2 L rows of a sequence of L "
                "that needs no padding, in blocks that divide it; got "
                f"causal={causal}, block_diffusion={block_diffusion}, {t} "
                "rows")
    if window is not None and (not causal or selection is not None
                               or int(window) < 1):
        raise ValueError("a window is causal, holds at least the query's "
                         "own position and comes without a selection; got "
                         f"causal={causal}, window={window}, selection "
                         f"{'given' if selection is not None else 'None'}")
    tp = _pad_len(t, interpret)
    if q.shape[2] % k.shape[2] or k.shape[2] != v.shape[2]:
        raise ValueError(f"{q.shape[2]} query heads over {k.shape[2]} key "
                         f"and {v.shape[2]} value heads")
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))  # → [B,H,T,D]
    if block_diffusion is not None:
        o, lse = _flash_block_diffusion(
            qt, kt, vt, block_q, block_k, interpret,
            _product_operand(interpret), (b, l))
        return o.transpose(0, 2, 1, 3), lse[..., 0]
    if selection is not None:
        if not causal or tp != t or select_from is None:
            raise ValueError("a selection is causal, says from which row on "
                             "it selects (select_from) and takes a sequence "
                             f"that needs no padding; got causal={causal}, "
                             f"select_from={select_from}, {t} rows")
        o, lse = _flash_selected(qt, kt, vt, selection, block_q, block_k,
                                 interpret, _product_operand(interpret),
                                 int(select_from))
        return o.transpose(0, 2, 1, 3), lse[..., 0]
    if tp != t:
        pad = [(0, 0), (0, 0), (0, tp - t), (0, 0)]
        qt, kt, vt = (jnp.pad(x, pad) for x in (qt, kt, vt))
    if window is None:
        o, lse = _flash(qt, kt, vt, causal, block_q, block_k, interpret, t,
                        _product_operand(interpret))
    else:
        o, lse = _flash_windowed(qt, kt, vt, block_q, block_k, interpret, t,
                                 _product_operand(interpret), int(window))
    if tp != t:
        o = o[:, :, :t, :]
        lse = lse[:, :, :t, :]
    return o.transpose(0, 2, 1, 3), lse[..., 0]


def flash_attention(q, k, v, causal: bool = False, *,
                    block_q: int = _DEFAULT_BLOCK,
                    block_k: int = _DEFAULT_BLOCK,
                    interpret: Optional[bool] = None,
                    selection: Optional[jax.Array] = None,
                    select_from: Optional[int] = None,
                    window: Optional[int] = None,
                    block_diffusion: Optional[Tuple[int, int]] = None
                    ) -> jax.Array:
    """Drop-in ``attn_fn(q, k, v, causal)``: q, k ``[B, T, H, Dk]``, v
    ``[B, T, H, Dv]`` → ``[B, T, H, Dv]``; the scale is 1/√Dk.  The two
    widths are usually one (D = 64 in the GPT-2 blocks); latent attention
    decompresses keys 192 wide beside values 128 wide, and the same
    kernels, block rule, transposed tiles and operand rounding serve both.

    Grouped queries: k and v may have ``Hkv`` heads where q has ``H``;
    query head ``j`` reads key/value head ``j // (H // Hkv)``.
    ``selection`` (:func:`pack_selection` of ``[B, T, T]``, with this
    call's ``block_k``): query ``t`` attends only to the keys marked for
    it, all of them at or before ``t``; ``select_from``: rows before it
    select every earlier key (a learned top-k's ``k``), so q blocks that
    end there take the causal tiles and never read the words.  EVERY
    causal tile of a later block is computed and masked: the kernel does
    not skip what was not chosen.

    ``window`` (static, causal only, not with a selection): query ``t``
    attends to keys ``t - window < s <= t``, its own position and the
    ``window - 1`` before it; tiles wholly behind the window are skipped,
    forward and backward.  None: every earlier key, as ever.

    ``block_diffusion=(B, L)`` (static, NOT causal, with neither of the
    above): the rows are a sequence of ``L`` tokens and, where ``T = 2 L``,
    its noised copy behind it; :func:`block_diffusion_mask` is what a row
    attends to.  The tiles divide ``L``; those the rule lets nothing
    through are skipped, forward and backward (:func:`pairs_computed`).

    Sequences whose length is not MXU-tileable are zero-padded to the next
    tileable length (masked inside the kernels; the pad is sliced off), so
    any length compiles on real TPU."""
    if interpret is None:
        interpret = _use_interpret()
    return _pad_and_run(q, k, v, causal, block_q, block_k, interpret,
                        selection, select_from, window, block_diffusion)[0]


def flash_attention_with_lse(q, k, v, causal: bool = False, *,
                             block_q: int = _DEFAULT_BLOCK,
                             block_k: int = _DEFAULT_BLOCK,
                             interpret: Optional[bool] = None):
    """Like :func:`flash_attention` but also returns the log-sum-exp of the
    attention logits, ``lse [B, H, T]`` (f32) — the quantity blockwise/ring
    compositions merge partial attention outputs with (Liu et al. 2023).
    Fully differentiable in both outputs: the backward folds the lse
    cotangent into the softmax-normalization term (``Δ − dlse``), reusing
    the same Pallas kernels."""
    if interpret is None:
        interpret = _use_interpret()
    return _pad_and_run(q, k, v, causal, block_q, block_k, interpret)


def make_flash_attention(mesh: Optional[Mesh] = None, *,
                         block_q: int = _DEFAULT_BLOCK,
                         block_k: int = _DEFAULT_BLOCK,
                         interpret: Optional[bool] = None,
                         window: Optional[int] = None) -> Callable:
    """Factory returning an ``attn_fn``; with ``window``, one whose causal
    calls attend to the last ``window`` positions (:func:`flash_attention`).

    With a mesh, the kernel runs inside ``shard_map`` manual over the
    ``data`` (batch dim) and ``model`` (heads dim) axes — a ``pallas_call``
    is a compiler black box that jax refuses to partition by itself
    ("Mosaic kernels cannot be automatically partitioned").  The
    mesh is ``mesh`` when given, else the mesh context of the trace the
    call happens in: a session's step sets its own
    (``kernel/graph_transformer.py``) and jax sets one inside any
    ``shard_map``, so the model-zoo default (built with no mesh) shards
    wherever it is traced.  With neither, the kernel runs unsharded.  The
    ``seq`` axis is not handled here: compose with ring attention
    (``parallel/ring_attention.py``) for sequence parallelism.

    The interpret-mode decision is resolved HERE, at construction — not at
    trace time — so the product behaves identically under AOT lowering and
    multi-backend use.
    """
    if interpret is None:
        interpret = _use_interpret()
    kw = dict(block_q=block_q, block_k=block_k, interpret=interpret,
              window=window)

    @functools.lru_cache(maxsize=None)
    def _sharded(causal: bool, axes_key: frozenset, over):
        spec = P(MESH_AXIS_DATA if MESH_AXIS_DATA in axes_key else None,
                 None,
                 MESH_AXIS_MODEL if MESH_AXIS_MODEL in axes_key else None,
                 None)
        fn = functools.partial(flash_attention, causal=causal, **kw)
        # check_vma off: pallas_call's out_shape carries no varying-axis
        # metadata, and the kernel is trivially per-shard (no collectives).
        # jit: eager shard_map with partial axis_names trips JAX's internal
        # unmatch path; under jit (inlined when already tracing) it is sound.
        return jax.jit(jax.shard_map(
            fn, mesh=over, in_specs=(spec, spec, spec), out_specs=spec,
            axis_names=set(axes_key), check_vma=False))

    def attn_fn(q, k, v, causal: bool):
        ambient = jax.sharding.get_abstract_mesh()
        over = mesh if mesh is not None else ambient
        manual_axes = set()
        if not over.empty:
            # Shard only over axes that evenly divide the local dim — e.g.
            # model.init traces with a tiny batch that the data axis may not
            # divide; that trace just runs the kernel unsharded.  Axes an
            # enclosing shard_map (the explicit-sync path) already
            # manualized are local here — re-sharding them would
            # double-split.
            for ax, dim in ((MESH_AXIS_DATA, q.shape[0]),
                            (MESH_AXIS_MODEL, q.shape[2])):
                size = over.shape.get(ax, 1)
                if (size > 1 and dim % size == 0
                        and ax not in ambient.manual_axes):
                    manual_axes.add(ax)
        if not manual_axes:
            return flash_attention(q, k, v, causal, **kw)
        return _sharded(causal, frozenset(manual_axes), over)(q, k, v)

    return attn_fn
