"""Ring attention: sequence-parallel exact attention over the ``seq`` axis.

Blockwise ring attention (Liu et al. 2023 "Ring Attention with Blockwise
Transformers"): each device holds a chunk of the sequence; K/V blocks rotate
around the ring via ``ppermute`` while a numerically stable online softmax
(flash-attention style running max/sum) accumulates the output.  Compute on
the current block overlaps (courtesy of XLA's latency-hiding scheduler) with
the ICI transfer of the next block, so sequence length scales linearly with
the number of chips at constant memory per chip.

Absent from the reference (no sequence-scaling machinery at all — SURVEY
§5.7); this is new first-class scope for the TPU build.

Layout convention: q/k/v are ``[batch, seq, heads, head_dim]``; inside the
ring step the local shard is ``[B, T_local, H, D]``.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from autodist_tpu.const import MESH_AXIS_SEQ

_NEG_INF = -1e30  # finite "minus infinity": keeps exp()/max() NaN-free


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool):
    """Runs on one device inside shard_map: q/k/v are local seq shards."""
    axis_size = lax.axis_size(axis_name)
    axis_index = lax.axis_index(axis_name)
    b, t_q, h, d = q.shape
    t_k = k.shape[1]
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    q32 = q.astype(jnp.float32)

    q_pos = axis_index * t_q + jnp.arange(t_q)  # global positions of queries
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def accumulate(step, o, l, m, k_blk, v_blk):
        """Online-softmax update with the K/V block originally owned by
        chunk (axis_index - step) mod axis_size."""
        j = (axis_index - step) % axis_size
        logits = jnp.einsum("bqhd,bkhd->bhqk", q32,
                            k_blk.astype(jnp.float32)) * scale
        if causal:
            k_pos = j * t_k + jnp.arange(t_k)
            allowed = k_pos[None, :] <= q_pos[:, None]  # [t_q, t_k]
            logits = jnp.where(allowed[None, None], logits, _NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))          # [B,H,Tq]
        p = jnp.exp(logits - m_new[..., None])               # [B,H,Tq,Tk]
        corr = jnp.exp(m - m_new)                            # [B,H,Tq]
        l_new = l * corr + p.sum(axis=-1)
        o_new = (o * corr[..., None]
                 + jnp.einsum("bhqk,bkhd->bhqd", p,
                              v_blk.astype(jnp.float32)))
        return o_new, l_new, m_new

    def body(step, carry):
        o, l, m, k_blk, v_blk = carry
        o, l, m = accumulate(step, o, l, m, k_blk, v_blk)
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return o, l, m, k_next, v_next

    # pcast-to-varying: the accumulators are per-shard values (varying over
    # the manual seq axis) even though their initial contents are constants.
    vary = lambda x: lax.pcast(x, axis_name, to="varying")  # noqa: E731
    o0 = vary(jnp.zeros((b, h, t_q, d), jnp.float32))
    l0 = vary(jnp.zeros((b, h, t_q), jnp.float32))
    m0 = vary(jnp.full((b, h, t_q), _NEG_INF, jnp.float32))
    # The last block computes outside the loop so no wasted final ppermute
    # rotates K/V that nothing consumes (a collective in the loop body can't
    # be dead-code-eliminated by XLA).
    o, l, m, k_last, v_last = lax.fori_loop(
        0, axis_size - 1, body, (o0, l0, m0, k, v))
    o, l, m = accumulate(axis_size - 1, o, l, m, k_last, v_last)
    out = o / jnp.maximum(l, 1e-30)[..., None]               # [B,H,Tq,D]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)         # [B,Tq,H,D]


def _ring_flash_local(q, k, v, *, axis_name: str, causal: bool,
                      block_q: int, block_k: int, interpret: bool):
    """Ring step with the Pallas flash kernel as the within-chip block
    computation (ring-flash: Liu et al. 2023 composition).  The kernel
    returns (o, lse); partial outputs merge in log-space:

        lse' = logaddexp(lse_a, lse_b)
        o'   = o_a·exp(lse_a − lse') + o_b·exp(lse_b − lse')

    For causal attention, K/V blocks from FUTURE chunks contribute nothing:
    their lse is masked to −inf so the merge is an exact no-op (the block
    still computes — the ring must stay uniform across devices — matching
    the dense ring's cost model)."""
    from autodist_tpu.ops.flash_attention import flash_attention_with_lse

    axis_size = lax.axis_size(axis_name)
    axis_index = lax.axis_index(axis_name)
    flash = functools.partial(flash_attention_with_lse, block_q=block_q,
                              block_k=block_k, interpret=interpret)

    # Step 0 — the diagonal block (my own K/V): within-chunk causal mask.
    o0, lse0 = flash(q, k, v, causal)
    acc = o0.astype(jnp.float32)                       # [B,Tq,H,D]
    lse_acc = lse0.transpose(0, 2, 1)                  # [B,Tq,H]

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def body(step, carry):
        acc, lse_acc, k_blk, v_blk = carry
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        j = (axis_index - step) % axis_size            # block owner
        o_b, lse_b = flash(q, k_blk, v_blk, False)     # full cross-block
        lse_b = lse_b.transpose(0, 2, 1)               # [B,Tq,H]
        if causal:
            # Future chunks (j > me) are fully masked out of the merge.
            lse_b = jnp.where(j <= axis_index, lse_b, _NEG_INF)
        lse_new = jnp.logaddexp(lse_acc, lse_b)
        w_acc = jnp.exp(lse_acc - lse_new)[..., None]
        w_b = jnp.exp(lse_b - lse_new)[..., None]
        acc = acc * w_acc + o_b.astype(jnp.float32) * w_b
        return acc, lse_new, k_blk, v_blk

    acc, lse_acc, _, _ = lax.fori_loop(
        1, axis_size, body, (acc, lse_acc, k, v))
    return acc.astype(q.dtype)


def make_ring_attention(mesh: Mesh, axis_name: str = MESH_AXIS_SEQ,
                        inner: str = "auto", block_q: int = 512,
                        block_k: int = 512,
                        interpret: Optional[bool] = None) -> Callable:
    """Returns an ``attn_fn(q, k, v, causal)`` drop-in for
    :func:`autodist_tpu.models.transformer.dense_attention`, sequence-parallel
    over ``axis_name``.  Call it on GLOBAL [B, T, H, D] tensors inside jit —
    the partial-manual shard_map manualizes only the seq axis, leaving
    data/model axes to GSPMD.

    ``inner`` selects the within-chip block computation: ``"dense"`` (the
    blockwise softmax in this module), ``"flash"`` (the Pallas kernel with
    log-space merging — HBM traffic linear in the LOCAL length too), or
    ``"auto"`` (flash on TPU, dense elsewhere; decided at construction)."""
    if inner == "auto":
        import jax as _jax
        inner = "flash" if _jax.devices()[0].platform == "tpu" else "dense"
    if inner not in ("dense", "flash"):
        raise ValueError(f"inner must be dense|flash|auto, got {inner!r}")
    if interpret is None and inner == "flash":
        from autodist_tpu.ops.flash_attention import _use_interpret
        interpret = _use_interpret()
    spec = P(None, axis_name, None, None)

    @functools.lru_cache(maxsize=None)
    def _flash_ring(causal: bool):
        # check_vma off: pallas_call's out_shape carries no varying-axis
        # metadata (vma tracking rejects it), and this ring needs no
        # auto-collectives — ppermute is explicit and the merge is purely
        # local.  jit (inlined when the caller already traces): eager
        # shard_map with partial axis_names trips JAX's internal unmatch
        # path (same workaround as ops/flash_attention.py); cached per
        # causal flag so eager callers keep a stable jit identity.
        local = functools.partial(
            _ring_flash_local, axis_name=axis_name, causal=causal,
            block_q=block_q, block_k=block_k, interpret=interpret)
        return jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(spec, spec, spec), out_specs=spec,
            axis_names={axis_name}, check_vma=False))

    def attn_fn(q, k, v, causal: bool):
        if mesh.shape.get(axis_name, 1) <= 1:
            from autodist_tpu.models.transformer import dense_attention
            return dense_attention(q, k, v, causal)
        if inner == "flash":
            return _flash_ring(bool(causal))(q, k, v)
        local = functools.partial(_ring_attention_local,
                                  axis_name=axis_name, causal=causal)
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(spec, spec, spec), out_specs=spec,
            axis_names={axis_name})(q, k, v)

    return attn_fn
