"""Flagship decoder-only language model.

This is the model the framework's multi-dimensional parallelism is exercised
on (dp/tp/sp/pp/ep in ``__graft_entry__.dryrun_multichip``): a GPT-style
causal LM whose embedding table is a sparse-gradient variable (Parallax PS
lowering shards it along the vocab axis) and whose attention implementation
is pluggable for sequence parallelism (ring attention).

The reference has no decoder LM — its sequence models are the lm1b LSTM and
BERT (SURVEY §5.7); this model is the new-scope flagship that the long-context
machinery requires.
"""
from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from autodist_tpu.models.base import ModelSpec, cross_entropy_loss
from autodist_tpu.models.transformer import TransformerStack, dense_attention
from autodist_tpu.telemetry import timeline


class TransformerLM(nn.Module):
    vocab_size: int
    num_layers: int
    num_heads: int
    head_dim: int
    d_ff: int
    max_len: int
    attn_fn: Callable = staticmethod(dense_attention)
    dtype: jnp.dtype = jnp.float32
    remat: str = "none"

    def setup(self):
        d_model = self.num_heads * self.head_dim
        self.embed = self.param("embed", nn.initializers.normal(0.02),
                                (self.vocab_size, d_model), self.dtype)
        self.pos_embed = self.param("pos_embed", nn.initializers.normal(0.02),
                                    (self.max_len, d_model), self.dtype)
        self.decoder = TransformerStack(
            self.num_layers, self.num_heads, self.head_dim, self.d_ff,
            causal=True, attn_fn=self.attn_fn, remat=self.remat)

    def features(self, tokens):
        """Pre-logits activations ``[B, T, D]`` — paired with the tied
        embedding through the chunked cross entropy when the training
        loss must not materialize ``[B, T, vocab]`` logits."""
        with jax.named_scope(timeline.SCOPE_LM_EMBED):
            x = (jnp.take(self.embed, tokens, axis=0)
                 + self.pos_embed[None, :tokens.shape[1]])
        return self.decoder(x)

    def __call__(self, tokens):
        # Tied output head: logits against the embedding table — keeps the
        # only vocab-sized variable the (sparse) embedding.
        feats = self.features(tokens)
        with jax.named_scope(timeline.SCOPE_LM_HEAD_LOSS):
            return jnp.einsum("btd,vd->btv", feats, self.embed)


def transformer_lm(vocab_size: int = 32128, num_layers: int = 12,
                   num_heads: int = 12, head_dim: int = 64,
                   d_ff: int = 3072, max_len: int = 1024,
                   attn_fn: Optional[Callable] = None,
                   dtype=jnp.float32, seq_len: Optional[int] = None,
                   xent_chunk: Optional[int] = None,
                   remat: str = "none") -> ModelSpec:
    """GPT-2-small-ish defaults; shrink for tests.

    ``attn_fn=None`` → backend default: the Pallas flash kernel on TPU,
    dense softmax elsewhere (``models/transformer.py:default_attention``).
    ``xent_chunk`` → train with the chunked-vocab cross entropy
    (``ops/chunked_xent.py``): the ``[B, T, vocab]`` logits never
    materialize — worth ~2 GB of peak HBM at batch 16 × seq 2048.
    ``remat`` → per-layer rematerialization ("none" | "dots" | "full",
    see ``TransformerStack.remat``): trade recompute FLOPs for
    activation HBM, usually to grow the batch into the freed memory."""
    from autodist_tpu.models.transformer import default_attention

    attn_fn = attn_fn or default_attention()
    seq_len = seq_len or max_len
    model = TransformerLM(vocab_size, num_layers, num_heads, head_dim, d_ff,
                          max_len, attn_fn=attn_fn, dtype=dtype,
                          remat=remat)

    def init(rng):
        tokens = jnp.zeros((2, seq_len), jnp.int32)
        return model.init(rng, tokens)["params"]

    def apply_fn(params, tokens):
        return model.apply({"params": params}, tokens)

    if xent_chunk:
        from autodist_tpu.ops.chunked_xent import \
            chunked_softmax_cross_entropy

        def loss_fn(params, batch):
            feats = model.apply({"params": params}, batch["tokens"],
                                method=TransformerLM.features)
            with jax.named_scope(timeline.SCOPE_LM_HEAD_LOSS):
                return chunked_softmax_cross_entropy(
                    feats[:, :-1], params["embed"], batch["tokens"][:, 1:],
                    chunk=xent_chunk)
    else:
        def loss_fn(params, batch):
            logits = apply_fn(params, batch["tokens"])
            with jax.named_scope(timeline.SCOPE_LM_HEAD_LOSS):
                return cross_entropy_loss(logits[:, :-1],
                                          batch["tokens"][:, 1:])

    def make_batch(rng: np.random.RandomState, batch_size: int):
        return {"tokens": rng.randint(
            0, vocab_size, (batch_size, seq_len)).astype(np.int32)}

    return ModelSpec(
        name="transformer_lm",
        init=init, loss_fn=loss_fn, apply_fn=apply_fn, make_batch=make_batch,
        sparse_vars=("embed",),
        config=dict(vocab_size=vocab_size, num_layers=num_layers,
                    num_heads=num_heads, head_dim=head_dim, d_ff=d_ff,
                    max_len=max_len, seq_len=seq_len),
    )
