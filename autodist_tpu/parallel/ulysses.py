"""Ulysses (DeepSpeed-style) sequence parallelism via all-to-all.

Alternative to ring attention: instead of rotating K/V blocks, a single
``all_to_all`` re-shards activations from sequence-sharded to head-sharded,
dense attention runs on full sequences for a subset of heads, and a second
``all_to_all`` restores sequence sharding.  Two collectives per attention
call, no per-block loop — typically faster than a ring when
``num_heads >= seq_axis_size`` and sequence fits per-device memory after the
head split.

Absent from the reference (SURVEY §5.7); new first-class scope.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from autodist_tpu.const import MESH_AXIS_SEQ


def _ulysses_local(q, k, v, *, axis_name: str, causal: bool,
                   inner_attn: Callable):
    """Inside shard_map: q/k/v are [B, T_local, H, D]."""
    # seq-sharded -> head-sharded: [B, T_global, H/n, D]
    def to_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    out = inner_attn(to_heads(q), to_heads(k), to_heads(v), causal)
    return to_seq(out)


def make_ulysses_attention(mesh: Mesh, axis_name: str = MESH_AXIS_SEQ,
                           inner: str = "auto", block_q: int = 512,
                           block_k: int = 512,
                           interpret: Optional[bool] = None) -> Callable:
    """Returns an ``attn_fn(q, k, v, causal)`` drop-in for dense_attention,
    sequence-parallel via all-to-all.  Requires num_heads divisible by the
    seq axis size.

    ``inner`` selects the full-sequence attention run per head subset
    between the two all-to-alls: ``"dense"``, ``"flash"`` (the Pallas
    kernel — the global sequence is what each device sees here, so the
    O(T²) HBM saving applies to the FULL length), or ``"auto"`` (flash on
    TPU, dense elsewhere; decided at construction)."""
    if inner == "auto":
        inner = "flash" if jax.devices()[0].platform == "tpu" else "dense"
    if inner not in ("dense", "flash"):
        raise ValueError(f"inner must be dense|flash|auto, got {inner!r}")
    from autodist_tpu.models.transformer import dense_attention

    if inner == "flash":
        from autodist_tpu.ops.flash_attention import (
            _use_interpret,
            flash_attention,
        )
        if interpret is None:
            interpret = _use_interpret()
        inner_fn = functools.partial(flash_attention, block_q=block_q,
                                     block_k=block_k, interpret=interpret)
    else:
        inner_fn = dense_attention
    spec = P(None, axis_name, None, None)

    @functools.lru_cache(maxsize=None)
    def _mapped(causal: bool):
        local = functools.partial(_ulysses_local, axis_name=axis_name,
                                  causal=causal, inner_attn=inner_fn)
        # jit + check_vma=False on the flash path (pallas out_shape carries
        # no vma; partial-axes eager shard_map needs the jit wrapper —
        # same workarounds as ring_attention.py).
        if inner == "flash":
            return jax.jit(jax.shard_map(
                local, mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=spec, axis_names={axis_name}, check_vma=False))
        return jax.shard_map(
            local, mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=spec, axis_names={axis_name})

    def attn_fn(q, k, v, causal: bool):
        n = mesh.shape.get(axis_name, 1)
        if n <= 1:
            return dense_attention(q, k, v, causal)
        if q.shape[2] % n != 0:
            raise ValueError(
                f"Ulysses needs num_heads ({q.shape[2]}) divisible by the "
                f"'{axis_name}' axis size ({n}); use ring attention instead")
        return _mapped(bool(causal))(q, k, v)

    return attn_fn
