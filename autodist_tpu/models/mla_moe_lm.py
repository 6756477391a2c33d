"""Decoder LM with latent attention and sigmoid-routed experts.

The DeepSeek-V3 block (arxiv 2412.19437; ``model_type: deepseek_v3``):

* **Multi-head latent attention** without the query low-rank: keys and
  values are decompressed from one ``kv_lora``-wide latent a token; a key
  is ``[k_nope (from the latent), k_rope]`` where the rotary part is ONE
  vector a token shared by all heads; queries and keys are ``nope + rope``
  wide and values ``v_head`` wide, so the attention call gets
  ``q, k: [B, T, H, Dk]`` and ``v: [B, T, H, Dv]`` with ``Dk != Dv``
  (``ops/flash_attention.py`` takes both; the scale is 1/sqrt(Dk)).
* RMSNorm before attention and FFN, on the latent, and at the end; rotary
  positions on interleaved pairs of the WEIGHTS' columns, which the
  kernel's queries and keys hold de-interleaved (``attention_operands``);
  SwiGLU; no bias; an untied head.
* ``first_dense`` leading layers with a dense SwiGLU, then expert layers
  (``parallel/moe.py: routed_moe_ffn``): ``top_k`` of ``num_experts`` by
  sigmoid score plus a selection bias, weights renormalised over the picks
  and scaled, NO token dropped, shared experts beside the routed ones.
  ``experts_held = (first, count)`` is this chip's share under expert
  parallelism: it routes over all and computes its own.

Built on ``models/routed_decoder.py``: attention one sequence at a time
(what it holds while it runs, queries, keys and values 192 and 128 wide in
float32, is then a sequence's: 4 x 4096 tokens at the benchmark's widths
ask for 20 GB otherwise), the experts once over ALL the sequences, a
sequence a slice.  Functional (plain parameter dicts), like ``moe_lm.py``:
every leaf has a strategy-addressable name, RMSNorm leaves are
``.../scale``.  The training path only: the latent is not cached and the
decode path that absorbs ``W_kvb`` into the query is not written (ROADMAP
M3).
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from autodist_tpu.models.base import ModelSpec, rms_norm, rotary_halves
from autodist_tpu.models.routed_decoder import routed_decoder
from autodist_tpu.ops.flash_attention import RESIDUAL_NAMES
from autodist_tpu.parallel.moe import (
    ROUTING_RESIDUAL_NAMES,
    init_routed_moe_params,
    routed_moe_ffn,
    swiglu,
)
from autodist_tpu.telemetry import timeline

# What a layer's checkpoints keep besides their inputs: what a kernel or a
# sort produced (dear to recompute, cheap to hold).
KEPT_NAMES = RESIDUAL_NAMES + ROUTING_RESIDUAL_NAMES


def _halves_first(w: jax.Array) -> jax.Array:
    """Interleaved pairs along the last axis to half-split order: the even
    columns, then the odd ones."""
    return jnp.concatenate(
        [jax.lax.slice_in_dim(w, first, None, stride=2, axis=-1)
         for first in (0, 1)], axis=-1)


def attention_operands(p: dict, qk_nope: int) -> dict:
    """The attention leaves cut the way ``latent_attention`` multiplies
    them, so that no per-head activation is sliced after its product wrote
    it: ``wq [D, H, nope + rope]`` into its nope and rope columns and
    ``wkv_b [latent, H, nope + Dv]`` into keys and values; the rope
    columns of ``wq`` and ``wkv_a`` gathered to half-split order
    (``rotary_halves``).  ``wkv_a`` stays one product: what it writes is
    one vector a token, not one a head.  Weight-sized work: done once a
    layer, outside the map over sequences and its checkpoint."""
    latent = p["wkv_b"].shape[0]
    return {"wq_nope": p["wq"][..., :qk_nope],
            "wq_rope": _halves_first(p["wq"][..., qk_nope:]),
            "wkv_a": jnp.concatenate(
                [p["wkv_a"][:, :latent],
                 _halves_first(p["wkv_a"][:, latent:])], axis=-1),
            "kv_norm": p["kv_norm"],
            "wk_b": p["wkv_b"][..., :qk_nope],
            "wv_b": p["wkv_b"][..., qk_nope:],
            "wo": p["wo"]}


def latent_attention(p: dict, x: jax.Array, attn_fn: Callable, *,
                     theta: float, eps: float) -> jax.Array:
    """``x [B, T, D] -> [B, T, D]``.  ``p``: ``attention_operands`` of the
    leaves ``wq [D, H, nope + rope]``, ``wkv_a [D, latent + rope]``,
    ``kv_norm/scale [latent]``, ``wkv_b [latent, H, nope + Dv]``, ``wo [H,
    Dv, D]``.  The kernel's queries and keys hold their rope columns
    de-interleaved, both alike: every score is the sum of the same
    products as with interleaved pairs."""
    heads, latent = p["wo"].shape[0], p["wk_b"].shape[0]
    with jax.named_scope(timeline.SCOPE_MLA_PROJECT):
        q = jnp.concatenate(
            [jnp.einsum("btd,dhk->bthk", x, p["wq_nope"]),
             rotary_halves(jnp.einsum("btd,dhk->bthk", x, p["wq_rope"]),
                           theta)], axis=-1)
        kv_a = x @ p["wkv_a"]
        c = rms_norm(kv_a[..., :latent], p["kv_norm"]["scale"], eps)
        k_rope = rotary_halves(kv_a[..., latent:], theta)    # [B, T, rope]
        k = jnp.concatenate(
            [jnp.einsum("btc,chk->bthk", c, p["wk_b"]),
             jnp.broadcast_to(k_rope[:, :, None, :],
                              k_rope.shape[:2] + (heads,)
                              + k_rope.shape[2:])], axis=-1)
        v = jnp.einsum("btc,chk->bthk", c, p["wv_b"])
    # the kernel's HLO name is the innermost scope: ``attn``, as in the
    # flax blocks (``MultiHeadAttention`` is named so)
    with jax.named_scope(timeline.SCOPE_MLA_ATTENTION), \
            jax.named_scope("attn"):
        o = attn_fn(q, k, v, True)                           # [B,T,H,Dv]
    with jax.named_scope(timeline.SCOPE_MLA_PROJECT):
        return jnp.einsum("bthv,hvd->btd", o, p["wo"])


def mla_moe_lm(vocab_size: int = 16032, num_layers: int = 5,
               first_dense: int = 1, d_model: int = 2048,
               num_heads: int = 32, qk_nope: int = 128, qk_rope: int = 64,
               v_head: int = 128, kv_lora: int = 512, d_ff: int = 6144,
               d_expert: int = 768, num_experts: int = 128,
               experts_held: Optional[Tuple[int, int]] = None,
               top_k: int = 6, shared_experts: int = 2,
               routed_scale: float = 2.448, rope_theta: float = 1e6,
               rms_eps: float = 1e-6, seq_len: int = 4096,
               attn_fn: Optional[Callable] = None, dtype=jnp.float32,
               xent_chunk: Optional[int] = None, remat: str = "full",
               train_router: bool = True,
               return_counts: bool = False) -> ModelSpec:
    """Defaults: one chip's share of kanana-2-30b-a3b cut to five layers
    (``benchmark/configs/kanana-2-30b-a3b.ep8-share.json`` passes
    ``experts_held=[0, 16]``); shrink every size for tests.

    ``experts_held=(first, count)``: the expert leaves lead with ``count``
    experts; None holds all.  ``remat``: per-layer rematerialisation,
    "none" | "full".  "full" here means: the backward recomputes a
    layer's halves EXCEPT what a kernel or a sort produced, which is kept
    by name (``KEPT_NAMES``: the flash kernel's ``o`` and ``lse``, a
    routed layer's picks, sort orders and group sizes), so the forward
    kernel, ``top_k`` and the sorts run once a layer, not twice; at the
    benchmark's widths that holds 67.9 MB a sequence and layer.  The gauge
    ``autodist_remat_kept_bytes_per_step{name}`` says what a step holds.
    ``xent_chunk``: the head's loss through ``ops/chunked_xent.py``.
    ``train_router=False``: the routers' weights take no gradient
    (``routed_moe_ffn``).
    ``return_counts``: ``loss_fn`` returns ``(loss, {"tokens_per_expert":
    [expert layers, count]})`` for ``capture(has_aux=True)``."""
    from autodist_tpu.models.transformer import default_attention

    if not 0 <= first_dense < num_layers:
        raise ValueError(f"{first_dense} dense layers of {num_layers}")
    attn_fn = attn_fn or default_attention()
    held = tuple(experts_held) if experts_held else (0, num_experts)
    d_qk = qk_nope + qk_rope

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
            k = jax.random.split(r, 8)
            layer = {
                "ln_attn": scale(d_model),
                "attn": {"wq": normal(k[0], d_model, num_heads, d_qk),
                         "wkv_a": normal(k[1], d_model, kv_lora + qk_rope),
                         "kv_norm": scale(kv_lora),
                         "wkv_b": normal(k[2], kv_lora, num_heads,
                                         qk_nope + v_head),
                         "wo": normal(k[3], num_heads, v_head, d_model)},
                "ln_mlp": scale(d_model)}
            if i < first_dense:
                layer["mlp"] = {"w_gate": normal(k[4], d_model, d_ff),
                                "w_up": normal(k[5], d_model, d_ff),
                                "w_down": normal(k[6], d_ff, d_model)}
            else:
                layer["moe"] = init_routed_moe_params(
                    k[7], d_model, d_expert, num_experts,
                    experts_held=held[1],
                    d_shared=shared_experts * d_expert, dtype=dtype)
            params[f"layers_{i}"] = layer
        return params

    def attention_half(lp, x):
        """One sequence ``[1, T, D]`` plus its attention; ``lp``: the
        layer's leaves with the attention's as ``attention_operands``
        (cut for their products once a layer, outside the map over
        sequences: inside it the cuts' transposes would pad and add
        weight-sized buffers every sequence of the backward)."""
        return x + latent_attention(
            lp["attn"], rms_norm(x, lp["ln_attn"]["scale"], rms_eps),
            attn_fn, theta=rope_theta, eps=rms_eps)

    def ffn_half(lp, x):
        """One sequence ``[1, T, D]`` of a leading layer plus its dense
        SwiGLU (a slice being a sequence here, the skeleton runs it in
        the attention half's map and under its checkpoint)."""
        with jax.named_scope(timeline.SCOPE_FFN_DENSE):
            return x + swiglu(lp["mlp"], rms_norm(
                x, lp["ln_mlp"]["scale"], rms_eps))

    def expert_half(lp, x):
        """ALL the sequences ``[B, T, D]`` plus their experts' output, and
        the tokens each held expert was sent: one sort a layer, each expert
        weight's gradient one product, and no buffer wider than a
        sequence's picks (``routed_moe_ffn``'s chunk)."""
        y, counts = routed_moe_ffn(
            lp["moe"], rms_norm(x, lp["ln_mlp"]["scale"], rms_eps),
            top_k=top_k, experts_held=held, routed_scale=routed_scale,
            train_router=train_router)
        return x + y, counts

    dense, routed = (attention_half, ffn_half), (attention_half, expert_half)
    return routed_decoder(
        name="mla_moe_lm", init=init,
        halves_of=lambda i: dense if i < first_dense else routed,
        operands_of=lambda lp: dict(lp, attn=attention_operands(
            lp["attn"], qk_nope)),
        dense_layers=tuple(range(first_dense)), kept_names=KEPT_NAMES,
        vocab_size=vocab_size, num_layers=num_layers, seq_len=seq_len,
        moe_slice=seq_len, top_k=top_k, num_experts=num_experts,
        rms_eps=rms_eps, xent_chunk=xent_chunk, remat=remat,
        return_counts=return_counts,
        config=dict(vocab_size=vocab_size, num_layers=num_layers,
                    first_dense=first_dense, d_model=d_model,
                    num_heads=num_heads, qk_nope=qk_nope, qk_rope=qk_rope,
                    v_head=v_head, kv_lora=kv_lora, d_ff=d_ff,
                    d_expert=d_expert, num_experts=num_experts,
                    experts_held=held, top_k=top_k,
                    shared_experts=shared_experts, seq_len=seq_len),
    )
