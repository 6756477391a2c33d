"""Pallas TPU flash attention (forward + backward kernels).

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
``[batch, seq, heads, head_dim]``; internally the kernel runs per (batch,
head) on ``[seq, head_dim]`` tiles.

Interpret mode (CPU tests) is selected automatically off-TPU.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.sharding import Mesh, PartitionSpec as P

from autodist_tpu.const import MESH_AXIS_DATA, MESH_AXIS_MODEL
from autodist_tpu.ops import pallas_utils

_NEG_INF = -1e30  # finite -inf: keeps exp()/max() NaN-free (masked rows)
# Tiling policy lives in ops/pallas_utils.py (shared by every Pallas
# kernel in the repo); these aliases keep this module's historical
# private names importable (tests pin the padding policy through them).
_TILE = pallas_utils.TILE
_pick_block = pallas_utils.pick_block
_pad_len = pallas_utils.pad_len
_use_interpret = pallas_utils.use_interpret
# Default q/k block edge.  Measured on TPU v5e (B=2,H=8,D=64, causal,
# fwd+bwd, vs XLA dense attention): 512 gives ~1.0x at T=2048, ~1.8x at
# T=4096, ~3.2x at T=8192; 128 loses to dense.  _pick_block degrades
# gracefully for sequences 512 doesn't divide.
_DEFAULT_BLOCK = 512


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal: bool,
                block_k: int, scale: float, kv_len: int):
    """One (batch, head, q-block) program: stream K/V blocks, online softmax.

    Refs: q [1,1,bq,D]; k/v [1,1,T,D]; o [1,1,bq,D]; lse [1,1,bq,1]
    (the trailing singleton keeps the block's last-two dims TPU-tileable).
    ``kv_len`` < T means the tail is alignment padding — masked out.
    """
    q = q_ref[0, 0].astype(jnp.float32) * scale            # [bq, D]
    bq, d = q.shape
    t_k = k_ref.shape[2]
    padded = kv_len < t_k
    num_kb = t_k // block_k
    qi = pl.program_id(2)
    q_pos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(kb, carry):
        o, l, m = carry
        k0 = kb * block_k
        k = k_ref[0, 0, pl.ds(k0, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(k0, block_k), :].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bq, bk]
        if causal or padded:
            k_pos = k0 + lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            mask = k_pos <= q_pos if causal else k_pos >= 0
            if padded:
                mask &= k_pos < kv_len
            s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))   # [bq,1]
        p = jnp.exp(s - m_new)                                  # [bq,bk]
        corr = jnp.exp(m - m_new)                               # [bq,1]
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        o_new = o * corr + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return o_new, l_new, m_new

    o0 = jnp.zeros((bq, d), jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    if causal:
        # Only K blocks at or before this q block's last row contribute.
        upper = lax.div(qi * bq + bq + block_k - 1, block_k)
        upper = jnp.minimum(upper, num_kb)
    else:
        upper = num_kb
    o, l, m = lax.fori_loop(0, upper, body, (o0, l0, m0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (o / l).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)


def _fwd(q, k, v, causal, block_q, block_k, interpret, kv_len):
    """q/k/v: [B, H, T, D] → (o [B,H,T,D], lse [B,H,T])."""
    b, h, t, d = q.shape
    bq = _pick_block(t, block_q)
    bk = _pick_block(t, block_k)
    scale = 1.0 / (d ** 0.5)
    grid = (b, h, t // bq)
    kernel = functools.partial(_fwd_kernel, causal=causal, block_k=bk,
                               scale=scale, kv_len=kv_len)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, t, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, t, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               causal: bool, block_k: int, scale: float, kv_len: int):
    """dQ for one q block: dS = P∘(dPᵀV − Δ); dQ = scale · dS·K."""
    q = q_ref[0, 0].astype(jnp.float32) * scale
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]                                     # [bq,1]
    delta = delta_ref[0, 0]                                 # [bq,1]
    bq, d = q.shape
    t_k = k_ref.shape[2]
    padded = kv_len < t_k
    num_kb = t_k // block_k
    qi = pl.program_id(2)
    q_pos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(kb, dq):
        k0 = kb * block_k
        k = k_ref[0, 0, pl.ds(k0, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(k0, block_k), :].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if causal or padded:
            k_pos = k0 + lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            mask = k_pos <= q_pos if causal else k_pos >= 0
            if padded:
                mask &= k_pos < kv_len
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse)                                # recomputed probs
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    if causal:
        upper = jnp.minimum(lax.div(qi * bq + bq + block_k - 1, block_k),
                            num_kb)
    else:
        upper = num_kb
    dq = lax.fori_loop(0, upper, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, causal: bool, block_q: int, scale: float,
                kv_len: int):
    """dK/dV for one k block: dV = PᵀdO; dK = scale · dSᵀQ."""
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    bk, d = k.shape
    t_q = q_ref.shape[2]
    padded = kv_len < t_q
    num_qb = t_q // block_q
    ki = pl.program_id(2)
    k_pos = ki * bk + lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)

    def body(qb, carry):
        dk, dv = carry
        q0 = qb * block_q
        q = q_ref[0, 0, pl.ds(q0, block_q), :].astype(jnp.float32) * scale
        do = do_ref[0, 0, pl.ds(q0, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(q0, block_q), :]          # [bq,1]
        delta = delta_ref[0, 0, pl.ds(q0, block_q), :]      # [bq,1]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bq,bk]
        if causal or padded:
            q_pos = q0 + lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
            mask = k_pos <= q_pos if causal else k_pos >= 0
            if padded:
                mask &= k_pos < kv_len
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dv = dv + lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk = dk + lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return dk, dv

    if causal:
        # q rows before this k block's first column are fully masked.
        lower = lax.div(ki * bk, block_q)
    else:
        lower = 0
    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    dk, dv = lax.fori_loop(lower, num_qb, body, (dk0, dv0))
    # q blocks were pre-scaled, so dSᵀQ already carries the 1/√d factor.
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, causal, block_q, block_k, interpret, kv_len,
         dlse=None):
    b, h, t, d = q.shape
    bq = _pick_block(t, block_q)
    bk = _pick_block(t, block_k)
    scale = 1.0 / (d ** 0.5)
    # Δ_i = Σ_d dO_id · O_id — the softmax-normalization gradient term;
    # a cheap elementwise reduce, left to XLA fusion.  [B,H,T,1] like lse.
    # An lse cotangent folds in here: dS_ij = P_ij (dP_ij − Δ_i + dlse_i),
    # so passing Δ' = Δ − dlse reuses the kernels unchanged.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    qb_spec = pl.BlockSpec((1, 1, bq, d), lambda bi, hi, i: (bi, hi, i, 0))
    kb_spec = pl.BlockSpec((1, 1, bk, d), lambda bi, hi, i: (bi, hi, i, 0))
    full_spec = pl.BlockSpec((1, 1, t, d), lambda bi, hi, i: (bi, hi, 0, 0))
    rowq_spec = pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, i: (bi, hi, i, 0))
    rowf_spec = pl.BlockSpec((1, 1, t, 1), lambda bi, hi, i: (bi, hi, 0, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, block_k=bk, scale=scale,
                          kv_len=kv_len),
        grid=(b, h, t // bq),
        in_specs=[qb_spec, full_spec, full_spec, qb_spec, rowq_spec,
                  rowq_spec],
        out_specs=qb_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, block_q=bq,
                          scale=scale, kv_len=kv_len),
        grid=(b, h, t // bk),
        in_specs=[full_spec, kb_spec, kb_spec, full_spec, rowf_spec,
                  rowf_spec],
        out_specs=[kb_spec, kb_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, t, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, t, d), v.dtype)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op ([B, T, H, D] layout, custom VJP)
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_k, interpret, kv_len):
    return _fwd(q, k, v, causal, block_q, block_k, interpret, kv_len)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, kv_len):
    o, lse = _fwd(q, k, v, causal, block_q, block_k, interpret, kv_len)
    return (o, lse), (q, k, v, o, lse)


def _flash_bwd(causal, block_q, block_k, interpret, kv_len, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    return _bwd(q, k, v, o, lse, do, causal, block_q, block_k, interpret,
                kv_len, dlse=dlse)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _pad_and_run(q, k, v, causal, block_q, block_k, interpret):
    """[B,T,H,D] public layout → padded [B,H,T,D] kernel run → sliced
    (o [B,T,H,D], lse [B,H,T])."""
    t = q.shape[1]
    tp = _pad_len(t, interpret)
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))  # → [B,H,T,D]
    if tp != t:
        pad = [(0, 0), (0, 0), (0, tp - t), (0, 0)]
        qt, kt, vt = (jnp.pad(x, pad) for x in (qt, kt, vt))
    o, lse = _flash(qt, kt, vt, causal, block_q, block_k, interpret, t)
    if tp != t:
        o = o[:, :, :t, :]
        lse = lse[:, :, :t, :]
    return o.transpose(0, 2, 1, 3), lse[..., 0]


def flash_attention(q, k, v, causal: bool = False, *,
                    block_q: int = _DEFAULT_BLOCK,
                    block_k: int = _DEFAULT_BLOCK,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Drop-in ``attn_fn(q, k, v, causal)`` on ``[B, T, H, D]`` tensors.

    Sequences whose length is not MXU-tileable are zero-padded to the next
    tileable length (masked inside the kernels; the pad is sliced off), so
    any length compiles on real TPU."""
    if interpret is None:
        interpret = _use_interpret()
    return _pad_and_run(q, k, v, causal, block_q, block_k, interpret)[0]


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
                         interpret: Optional[bool] = None) -> Callable:
    """Factory returning an ``attn_fn``.

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
    kw = dict(block_q=block_q, block_k=block_k, interpret=interpret)

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
