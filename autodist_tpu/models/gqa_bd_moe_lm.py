"""Decoder LM with grouped-query heads and softmax-routed experts, trained
by diffusion over blocks.

The language model of SDAR (``model_type: sdar_moe``; arXiv:2510.06303): a
Qwen3-MoE block, ``gqa_dsa_moe_lm``'s without the indexer, under the
block-diffusion objective and the two-copy pass of Arriola et al. 2025
("Block Diffusion", arXiv:2503.09573, Algorithm 1 and section 3.2).

* **Noise** (:func:`noise_of`).  A sequence ``x`` of ``L`` tokens lies in
  blocks of ``B``; block ``b`` draws a level ``t_b ~ U[eps, 1]`` and token
  ``i`` is masked with probability ``t_{i // B}``: ``x~_i = MASK`` where
  masked, else ``x_i``.  A batch that brings ``noised`` and
  ``loss_weight`` (a user's data pipeline) is taken at its word; for one
  that does not, a sequence's levels and masks come from a key folded from
  ``noise_seed`` and that sequence's OWN tokens, so the draw is the same
  however a step is cut into microbatches or over chips.
* **One pass over 2 L rows**: ids ``[x ; x~]`` at positions ``[0..L-1 ;
  0..L-1]`` (a noised row shares its clean twin's rotary position), under
  ``ops/flash_attention.py``'s ``block_diffusion=(B, L)`` mask: a clean row
  sees the clean rows of its own block and of those before it, a noised
  row the clean rows of the blocks before its twin's and the noised rows
  of its own block, both ways.  Nothing of the noised half reaches a clean
  row.
* **Loss** over the noised half only: row ``L + i`` predicts ``x_i``
  itself (no shift), weighted ``m_i / t_{i // B}``, over all ``rows * L``
  tokens.  The head runs on ``L`` rows, the layers on ``2 L``.
* **Heads and experts** as ``gqa_dsa_moe_lm``: RMSNorm over every query and
  key head, rotary by halves, query head ``j`` on key/value head ``j // (H
  // G)``; ``routed_moe_ffn(scoring="softmax")``, no token dropped,
  ``experts_held`` this chip's share.

``apply_fn(params, tokens [B, L])`` is the clean pass alone (block-causal
logits).  Built on ``models/routed_decoder.py`` through its ``objective``
seam; functional, the training path only (generation a block a step is
not written: ``ROADMAP.md``).
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from autodist_tpu.models.base import (
    ModelSpec,
    cross_entropy_loss,
    rms_norm,
    rotary_halves,
)
from autodist_tpu.models.routed_decoder import Objective, routed_decoder
from autodist_tpu.models.transformer import default_sparse_attention
from autodist_tpu.ops.flash_attention import (
    _DEFAULT_BLOCK,
    RESIDUAL_NAMES,
    pairs_computed,
)
from autodist_tpu.parallel.moe import (
    ROUTING_RESIDUAL_NAMES,
    init_routed_moe_params,
    routed_moe_ffn,
)
from autodist_tpu.telemetry import registry, step_values, timeline

KEPT_NAMES = RESIDUAL_NAMES + ROUTING_RESIDUAL_NAMES


def noise_of(tokens: jax.Array, *, block_length: int, eps: float,
             mask_id: int, seed: int) -> Tuple[jax.Array, jax.Array]:
    """``(noised [B, L] int32, weight [B, L] float32)`` of ``tokens [B,
    L]``, a sequence at a time: the key is ``fold_in(key(seed), d)`` with
    ``d = sum_i (x_i + 1) (2 i + 1)`` in wrapping uint32; split in two, the
    first half draws the blocks' levels ``t = eps + (1 - eps) u``, the
    second a uniform a token, masked where it is below its block's level;
    the weight is ``1 / t`` where masked and 0 elsewhere."""
    length = tokens.shape[1]
    odd = 2 * jnp.arange(length, dtype=jnp.uint32) + 1

    def one(row):
        digest = jnp.sum((row.astype(jnp.uint32) + 1) * odd, dtype=jnp.uint32)
        k_level, k_mask = jax.random.split(
            jax.random.fold_in(jax.random.key(seed), digest))
        level = jnp.repeat(eps + (1.0 - eps) * jax.random.uniform(
            k_level, (length // block_length,), jnp.float32), block_length)
        masked = jax.random.uniform(k_mask, (length,), jnp.float32) < level
        return (jnp.where(masked, mask_id, row).astype(jnp.int32),
                jnp.where(masked, 1.0 / level, 0.0))

    return jax.vmap(one)(tokens)


def gqa_bd_moe_lm(vocab_size: int = 18992, num_layers: int = 4,
                  d_model: int = 2048, num_heads: int = 32,
                  num_kv_heads: int = 4, head_dim: int = 128,
                  block_length: int = 4, noise_eps: float = 1e-3,
                  noise_seed: int = 0, mask_id: Optional[int] = None,
                  d_expert: int = 768, num_experts: int = 128,
                  experts_held: Optional[Tuple[int, int]] = None,
                  top_k: int = 8, rope_theta: float = 1e6,
                  rms_eps: float = 1e-6, seq_len: int = 8192,
                  attn_fn: Optional[Callable] = None,
                  block_k: int = _DEFAULT_BLOCK, moe_slice: int = 4096,
                  dtype=jnp.float32, xent_chunk: Optional[int] = None,
                  remat: str = "full", train_router: bool = True,
                  embed_scale: float = 1.0,
                  return_counts: bool = False) -> ModelSpec:
    """Defaults: one chip's share of SDAR-30B-A3B-Chat cut to four layers
    (``benchmark/configs/sdar-30b-a3b-chat.ep8-share.json`` passes
    ``experts_held=[0, 16]``); shrink every size for tests.

    ``seq_len`` is ``L``: the mask is a function of it, so a batch's
    sequences are this long (``block_length`` divides it).  ``mask_id``:
    the table's row a masked token reads, the last one by default.
    ``noise_eps``, ``noise_seed``: :func:`noise_of`.  ``attn_fn(q, k, v,
    False, block_diffusion=(block_length, seq_len))`` over ``L`` or ``2 L``
    rows.  ``embed_scale``: the stream enters layer 0 as this times the
    table's rows, as in ``swa_moe_lm`` (1: the model as published; a large
    one makes a row's OWN token most of what every router reads).
    ``remat``, ``moe_slice``, ``experts_held``, ``xent_chunk``,
    ``train_router``, ``return_counts``: as ``gqa_dsa_moe_lm``."""
    if num_heads % num_kv_heads:
        raise ValueError(f"{num_heads} query heads over {num_kv_heads}")
    if seq_len % block_length:
        raise ValueError(f"blocks of {block_length} in {seq_len} tokens")
    attn_fn = attn_fn or default_sparse_attention(block_k)
    held = tuple(experts_held) if experts_held else (0, num_experts)
    mask_id = vocab_size - 1 if mask_id is None else mask_id
    noise = dict(block_length=block_length, eps=noise_eps, mask_id=mask_id,
                 seed=noise_seed)

    def init(rng):
        def normal(key, *shape):
            return jax.random.normal(key, shape, dtype) * 0.02

        def scale(width):
            return {"scale": jnp.ones((width,), dtype)}

        r_emb, r_head, r_layers = jax.random.split(rng, 3)
        params = {"embed": normal(r_emb, vocab_size, d_model),
                  "head": normal(r_head, vocab_size, d_model),
                  "ln_final": scale(d_model)}
        for i, r in enumerate(jax.random.split(r_layers, num_layers)):
            k = jax.random.split(r, 5)
            params[f"layers_{i}"] = {
                "ln_attn": scale(d_model),
                "attn": {"wq": normal(k[0], d_model, num_heads, head_dim),
                         "wk": normal(k[1], d_model, num_kv_heads, head_dim),
                         "wv": normal(k[2], d_model, num_kv_heads, head_dim),
                         "q_norm": scale(head_dim),
                         "k_norm": scale(head_dim),
                         "wo": normal(k[3], num_heads, head_dim, d_model)},
                "ln_mlp": scale(d_model),
                "moe": init_routed_moe_params(
                    k[4], d_model, d_expert, num_experts,
                    experts_held=held[1], selection_bias=False, dtype=dtype)}
        return params

    def copies_of(rows: int) -> int:
        """1 where ``rows`` are a sequence, 2 with its noised copy."""
        if rows not in (seq_len, 2 * seq_len):
            raise ValueError(f"{rows} rows: a sequence has {seq_len}, with "
                             f"its noised copy {2 * seq_len}")
        return rows // seq_len

    def attention_half(lp, x):
        """``x [B, R, D]`` plus its attention."""
        copies_of(x.shape[1])
        h = rms_norm(x, lp["ln_attn"]["scale"], rms_eps)
        p = lp["attn"]
        with jax.named_scope(timeline.SCOPE_GQA_PROJECT):
            # a noised row stands at its clean twin's position
            at = jnp.arange(x.shape[1]) % seq_len
            q, k = (rotary_halves(rms_norm(
                jnp.einsum("btd,dhk->bthk", h, p[w]), p[n]["scale"],
                rms_eps), rope_theta, at)
                for w, n in (("wq", "q_norm"), ("wk", "k_norm")))
            v = jnp.einsum("btd,dhk->bthk", h, p["wv"])
        # the kernel's HLO name is the innermost scope
        with jax.named_scope(timeline.SCOPE_BD_ATTENTION), \
                jax.named_scope("bd_attn"):
            o = attn_fn(q, k, v, False,
                        block_diffusion=(block_length, seq_len))
        with jax.named_scope(timeline.SCOPE_GQA_PROJECT):
            return x + jnp.einsum("bthv,hvd->btd", o, p["wo"])

    def expert_half(lp, x):
        """``x [slices, slice, D]`` plus its experts' output, and the
        tokens each held expert was sent."""
        y, counts = routed_moe_ffn(
            lp["moe"], rms_norm(x, lp["ln_mlp"]["scale"], rms_eps),
            top_k=top_k, experts_held=held, train_router=train_router,
            scoring="softmax")
        return x + y, counts

    def set_gauges(ids):
        batch, rows = ids.shape
        copies = copies_of(rows)
        bd = (block_length, seq_len)
        for kind, pairs in (
                ("attended", copies * seq_len * (seq_len + block_length) // 2),
                ("computed", pairs_computed(rows, block_k=block_k,
                                            block_diffusion=bd))):
            registry.gauge(
                "autodist_bd_pairs_per_step",
                "pairs of query and key a step's attention is asked for "
                "under the block-diffusion mask (forward, over layers, "
                "heads and the batch: L (L + B) / 2 in each half), and "
                "pairs whose score its kernel forms (the tiles its loops "
                "visit, whole)", {"kind": kind}).set(
                pairs * batch * num_heads * num_layers)
        for kind, count in (("data", seq_len), ("layers", rows),
                            ("head", seq_len)):
            registry.gauge(
                "autodist_bd_rows_per_step",
                "rows of a step: the data tokens of its batch, the rows "
                "that go through every layer (with their noised copies) "
                "and the rows the head and the loss read",
                {"kind": kind}).set(count * batch)

    def publish_masked(masked):      # a scalar, stacked over microbatches
        registry.gauge(
            "autodist_bd_masked_tokens_per_step",
            "tokens of the last step's batch that the noise masked: the "
            "rows whose loss has a weight").set(int(np.sum(masked)))

    def rows(batch):
        tokens = batch["tokens"]
        if tokens.shape[1] != seq_len:
            raise ValueError(f"sequences of {tokens.shape[1]} tokens: the "
                             f"model's mask is built for {seq_len}")
        with jax.named_scope(timeline.SCOPE_BD_NOISE):
            if "noised" in batch:
                noised, weight = batch["noised"], batch["loss_weight"]
            else:
                noised, weight = noise_of(tokens, **noise)
            ids = jnp.concatenate([tokens, noised], axis=1)
            step_values.emit("bd_masked_tokens", jnp.sum(weight > 0),
                             publish_masked)
        return ids, (tokens, weight)

    def loss(feats, head, carried):
        tokens, weight = carried
        feats = feats[:, seq_len:]       # the noised half: L rows of 2 L
        if xent_chunk:
            from autodist_tpu.ops.chunked_xent import \
                chunked_softmax_cross_entropy

            return chunked_softmax_cross_entropy(
                feats, head, tokens, chunk=xent_chunk, weights=weight)
        logits = jnp.einsum("btd,vd->btv", feats, head)
        return cross_entropy_loss(logits, tokens, weight)

    return routed_decoder(
        name="gqa_bd_moe_lm", init=init,
        halves_of=lambda i: (attention_half, expert_half),
        kept_names=KEPT_NAMES, set_pairs_gauges=set_gauges,
        objective=Objective(rows=rows, loss=loss),
        vocab_size=vocab_size, num_layers=num_layers, seq_len=seq_len,
        moe_slice=moe_slice, top_k=top_k, num_experts=num_experts,
        rms_eps=rms_eps, xent_chunk=xent_chunk, remat=remat,
        return_counts=return_counts, embed_scale=embed_scale,
        config=dict(vocab_size=vocab_size, num_layers=num_layers,
                    d_model=d_model, num_heads=num_heads,
                    num_kv_heads=num_kv_heads, head_dim=head_dim,
                    embed_scale=embed_scale, block_length=block_length, noise_eps=noise_eps,
                    noise_seed=noise_seed, mask_id=mask_id,
                    d_expert=d_expert, num_experts=num_experts,
                    experts_held=held, top_k=top_k, seq_len=seq_len),
    )
