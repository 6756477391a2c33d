"""Shared transformer components (TPU-first).

Design notes:
* dims default to multiples of 128 so matmuls tile the MXU exactly;
* attention is a pluggable function so sequence-parallel implementations
  (ring attention, Ulysses — ``autodist_tpu/parallel/``) can replace the
  dense softmax without touching the model;
* parameter names are stable strategy keys (e.g. ``layers_0/attn/query/kernel``)
  — the analog of the reference's TF variable names in strategy node_configs.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from autodist_tpu.ops.flash_attention import (
    _DEFAULT_BLOCK,
    block_diffusion_mask,
    flash_attention,
    unpack_selection,
)
from autodist_tpu.ops.pallas_utils import use_interpret
from autodist_tpu.telemetry import timeline


@functools.cache
def _resolve_default_attention() -> Callable:
    """Resolved once per process, and logged then with the platform, so a
    run's log says which attention it ran."""
    from autodist_tpu.utils import logging

    platform = jax.devices()[0].platform
    if platform == "tpu":
        from autodist_tpu.ops.flash_attention import make_flash_attention

        logging.info("default attention: Pallas flash kernel (platform %s)",
                     platform)
        return make_flash_attention()
    logging.info("default attention: dense softmax (platform %s)", platform)
    return dense_attention


def default_attention() -> Callable:
    """The attention implementation for the current backend: the Pallas
    flash kernel on TPU — the hot-op fast path
    (``autodist_tpu/ops/flash_attention.py``) — and dense softmax attention
    elsewhere.  Model factories use this when no explicit ``attn_fn`` is
    passed.

    Resolved at the first CALL (trace time), never at construction: a
    multi-node script builds its model BEFORE
    ``jax.distributed.initialize``, and probing devices here would
    initialize the local backend and break the distributed bootstrap
    (``cluster.py:128-146``).  Trace time is also when the mesh is known:
    the flash kernel shards itself over the mesh context of the trace,
    which a session's step sets (``kernel/graph_transformer.py``)."""
    def attn(q, k, v, causal: bool):
        return _resolve_default_attention()(q, k, v, causal)

    return attn


def dense_attention(q, k, v, causal: bool) -> jax.Array:
    """Reference attention: softmax(QKᵀ/√d)V.  [B, T, H, D] layout."""
    depth = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(depth).astype(q.dtype)
    if causal:
        t_q, t_k = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((t_q, t_k), bool))
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def dense_selected_attention(q, k, v, causal, *, selection=None,
                             select_from=None, window=None,
                             block_diffusion=None, block_k=_DEFAULT_BLOCK):
    """What the kernel computes, by the plain softmax over all pairs (off
    the TPU, at a size a test holds): ``selection``, ``window`` and
    ``block_diffusion`` as the kernel takes them."""
    t, group = q.shape[1], q.shape[2] // k.shape[2]
    if block_diffusion is not None:
        mask = block_diffusion_mask(*block_diffusion, t)[None]
    elif selection is None:
        mask = jnp.tril(jnp.ones((t, t), bool))[None]
    else:
        mask = unpack_selection(selection, block_k=block_k)
    if window is not None:
        mask = mask & ~jnp.tril(jnp.ones((t, t), bool), -window)
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (q.shape[-1] ** 0.5)
    logits = jnp.where(mask[:, None], logits.astype(jnp.float32), -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(logits, axis=-1).astype(q.dtype), v)


def default_sparse_attention(block_k: int = _DEFAULT_BLOCK) -> Callable:
    """The flash kernel on a TPU, the dense softmax elsewhere; resolved at
    the first call, as ``default_attention`` is."""
    def attn(q, k, v, causal, **selection):
        if use_interpret():
            return dense_selected_attention(q, k, v, causal, block_k=block_k,
                                            **selection)
        return flash_attention(q, k, v, causal, block_k=block_k, **selection)

    return attn


class MultiHeadAttention(nn.Module):
    num_heads: int
    head_dim: int
    causal: bool = False
    attn_fn: Callable = staticmethod(dense_attention)

    @nn.compact
    def __call__(self, x):
        d = self.num_heads * self.head_dim
        proj = lambda name: nn.DenseGeneral(  # noqa: E731
            (self.num_heads, self.head_dim), use_bias=False, name=name)
        with jax.named_scope(timeline.SCOPE_MHA_PROJECT):
            q, k, v = proj("query")(x), proj("key")(x), proj("value")(x)
        # the kernel's HLO name is the innermost scope, and the benchmark's
        # readers find it by ``attn``
        with jax.named_scope(timeline.SCOPE_MHA_ATTENTION), \
                jax.named_scope("attn"):
            out = self.attn_fn(q, k, v, self.causal)
        with jax.named_scope(timeline.SCOPE_MHA_PROJECT):
            return nn.DenseGeneral(x.shape[-1], axis=(-2, -1),
                                   use_bias=False, name="out")(out)


class MlpBlock(nn.Module):
    d_ff: int

    @nn.compact
    def __call__(self, x):
        with jax.named_scope(timeline.SCOPE_FFN_DENSE):
            h = nn.Dense(self.d_ff, use_bias=False, name="wi")(x)
            h = nn.gelu(h)
            return nn.Dense(x.shape[-1], use_bias=False, name="wo")(h)


class TransformerLayer(nn.Module):
    num_heads: int
    head_dim: int
    d_ff: int
    causal: bool = False
    attn_fn: Callable = staticmethod(dense_attention)

    @nn.compact
    def __call__(self, x):
        h = nn.LayerNorm(name="ln_attn", use_bias=False)(x)
        x = x + MultiHeadAttention(self.num_heads, self.head_dim, self.causal,
                                   attn_fn=self.attn_fn, name="attn")(h)
        h = nn.LayerNorm(name="ln_mlp", use_bias=False)(x)
        x = x + MlpBlock(self.d_ff, name="mlp")(h)
        return x


class TransformerStack(nn.Module):
    num_layers: int
    num_heads: int
    head_dim: int
    d_ff: int
    causal: bool = False
    attn_fn: Callable = staticmethod(dense_attention)
    # Per-layer rematerialization: "none" keeps all activations; "full"
    # recomputes the whole layer in the backward pass (max memory saving,
    # +1 forward of FLOPs); "dots" saves matmul outputs and recomputes
    # the cheap elementwise tail (the usual MFU sweet spot: batch can
    # grow into the freed HBM while the recompute rides the idle MXU).
    remat: str = "none"

    @nn.compact
    def __call__(self, x):
        if self.remat not in ("none", "full", "dots"):
            raise ValueError(f"remat={self.remat!r}: expected 'none', "
                             f"'full', or 'dots'")
        layer_cls = TransformerLayer
        if self.remat != "none":
            policy = {
                "full": None,
                "dots": jax.checkpoint_policies.checkpoint_dots,
            }[self.remat]
            layer_cls = nn.remat(TransformerLayer, policy=policy,
                                 prevent_cse=False)
        with jax.named_scope(timeline.SCOPE_LM_LAYERS):
            for i in range(self.num_layers):
                x = layer_cls(self.num_heads, self.head_dim, self.d_ff,
                              self.causal, attn_fn=self.attn_fn,
                              name=f"layers_{i}")(x)
        with jax.named_scope(timeline.SCOPE_LM_HEAD_LOSS):
            return nn.LayerNorm(name="ln_final", use_bias=False)(x)
