"""Decoder LM whose layers are of two kinds by index, WINDOW layers
(rotary, a sliding window of keys) among GLOBAL layers (every earlier key,
no positions at all), with a router that reads the layer's input BEFORE
attention and ReLU-gated experts.

SmallThinker-21BA3B (PowerInfer, ``model_name: smallthinker_21b_instruct``).
``x_l [T, D]`` enters layer ``l``; ``sliding_window_layout[l]`` and
``rope_layout[l]`` say its kind (both 0: global; both 1: window):

    r   = x_l W_r                    float32 at ``highest``: the layer's INPUT
    h   = RMSNorm(x_l)
    q   = h W_q [T, H, Dh],  k = h W_k,  v = h W_v [T, G, Dh]     no bias,
                                                          no per-head norm
    q,k = rotary(q), rotary(k)       where rope_layout[l]: halves turned
                                     against each other (``rotary_halves``)
    a_t = softmax over s <= t, and t - window < s where
          sliding_window_layout[l], of q_t . k_s / sqrt(Dh), applied to v;
          query head j reads key/value head j // (H // G)
    u   = x_l + concat(a) W_o
    m   = RMSNorm(u)
    p   = softmax(r) over all E;  S = the top-k of p;  g_e = p_e / sum_S p
          (computed as the top-k of r and a softmax over the picked logits:
          the same numbers, and no tie where float32 underflows p to 0)
    x_{l+1} = u + sum_{e in S, held} g_e (relu(m W_gate,e) * (m W_up,e)) W_down,e

* **Attention** is ``ops/flash_attention.py`` with a static ``window`` in
  window layers: tiles wholly behind the window are skipped, forward and
  backward (``autodist_swa_pairs_per_step`` says how many pairs the
  kernels form beside the pairs attended to).  The kernel's HLO name is
  ``window_attn`` or ``global_attn`` under the scope ``swa/attention``.
* **Experts** are ``parallel/moe.py: routed_moe_ffn(scoring=
  "softmax_of_picked", router_input=x_l, activation=jax.nn.relu)``: the
  router's logits are those of the layer's input, which the expert half is
  handed slice by slice beside the stream it adds to (it is the attention
  half's own input, so nothing more is kept for it), and pass their
  gradient to it.
  ``router_before_attention=False`` is the usual placement: the router
  reads what its experts read.

Built on ``routed_decoder.routed_decoder`` (the halves under their
checkpoints, the slices, the loss, the gauges, the batch); functional,
the training path only.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from autodist_tpu.models.base import ModelSpec, rms_norm, rotary_halves
from autodist_tpu.models.routed_decoder import routed_decoder
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
from autodist_tpu.telemetry import registry, timeline

KEPT_NAMES = RESIDUAL_NAMES + ROUTING_RESIDUAL_NAMES


def attended_pairs(t: int, window: Optional[int]) -> int:
    """Pairs of query and key one head attends to over one sequence:
    ``sum_t min(t + 1, window)``; None: the causal triangle."""
    whole = t if window is None else min(t, window)
    return whole * (whole + 1) // 2 + (t - whole) * whole


def swa_moe_lm(vocab_size: int = 18992, num_layers: int = 4,
               d_model: int = 2560, num_heads: int = 28,
               num_kv_heads: int = 4, head_dim: int = 128,
               window: int = 4096,
               window_layout: Sequence[int] = (0, 1, 1, 1),
               rope_layout: Sequence[int] = (0, 1, 1, 1),
               d_expert: int = 768, num_experts: int = 64,
               experts_held: Optional[Tuple[int, int]] = None,
               top_k: int = 6, rope_theta: float = 1.5e6,
               rms_eps: float = 1e-6, seq_len: int = 16384,
               attn_fn: Optional[Callable] = None,
               block_k: int = _DEFAULT_BLOCK, moe_slice: int = 4096,
               dtype=jnp.float32, xent_chunk: Optional[int] = None,
               remat: str = "full", train_router: bool = True,
               router_before_attention: bool = True,
               embed_scale: float = 1.0,
               return_counts: bool = False) -> ModelSpec:
    """Defaults: one chip's share of SmallThinker-21BA3B-Instruct cut to
    its first period of four layers (``benchmark/configs/smallthinker-21b
    -a3b.ep8-share.json`` passes ``experts_held=[0, 8]``); shrink every
    size for tests.

    ``window_layout[l]`` / ``rope_layout[l]``: 1 where layer ``l`` attends
    through the ``window`` / turns its queries and keys by the rotary
    (the source's ``sliding_window_layout`` and ``rope_layout``, one entry
    a layer).  ``attn_fn(q, k, v, True, window=window)`` in window layers,
    ``attn_fn(q, k, v, True)`` in global ones.  ``embed_scale``: the
    stream enters layer 0 as this times the table's rows (1: the model as
    published; the routers read the un-normed stream, so this sets how
    much of what they read is the token's own row).  ``remat``,
    ``experts_held``, ``xent_chunk``, ``train_router``, ``return_counts``,
    ``moe_slice``: as ``gqa_dsa_moe_lm``."""
    if num_heads % num_kv_heads:
        raise ValueError(f"{num_heads} query heads over {num_kv_heads}")
    if not len(window_layout) == len(rope_layout) == num_layers:
        raise ValueError(f"{num_layers} layers, but window_layout has "
                         f"{len(window_layout)} entries and rope_layout "
                         f"{len(rope_layout)}")
    attn_fn = attn_fn or default_sparse_attention(block_k)
    held = tuple(experts_held) if experts_held else (0, num_experts)

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
                         "wo": normal(k[3], num_heads, head_dim, d_model)},
                "ln_mlp": scale(d_model),
                "moe": init_routed_moe_params(
                    k[4], d_model, d_expert, num_experts,
                    experts_held=held[1], selection_bias=False, dtype=dtype)}
        return params

    def attention_half_of(windowed: bool, turned: bool):
        def attention_half(lp, x):
            """``x [B, T, D]`` plus its attention."""
            h = rms_norm(x, lp["ln_attn"]["scale"], rms_eps)
            p = lp["attn"]
            with jax.named_scope(timeline.SCOPE_GQA_PROJECT):
                q, k, v = (jnp.einsum("btd,dhk->bthk", h, p[w])
                           for w in ("wq", "wk", "wv"))
                if turned:
                    q, k = (rotary_halves(y, rope_theta) for y in (q, k))
            # the kernel's HLO name is the innermost scope
            with jax.named_scope(timeline.SCOPE_SWA_ATTENTION), \
                    jax.named_scope("window_attn" if windowed
                                    else "global_attn"):
                o = attn_fn(q, k, v, True, window=window) if windowed \
                    else attn_fn(q, k, v, True)
            with jax.named_scope(timeline.SCOPE_GQA_PROJECT):
                return x + jnp.einsum("bthv,hvd->btd", o, p["wo"])

        return attention_half

    def expert_half(lp, x, entered=None):
        """``x [slices, slice, D]`` plus its experts' output, and the
        tokens each held expert was sent; ``entered``: the same tokens as
        they entered the layer, which the router reads."""
        y, counts = routed_moe_ffn(
            lp["moe"], rms_norm(x, lp["ln_mlp"]["scale"], rms_eps),
            top_k=top_k, experts_held=held, train_router=train_router,
            scoring="softmax_of_picked", router_input=entered,
            activation=jax.nn.relu)
        return x + y, counts

    kinds = {kind: (attention_half_of(*kind), expert_half)
             for kind in set(zip(map(bool, window_layout),
                                 map(bool, rope_layout)))}

    def set_pairs_gauges(tokens):
        batch, t = tokens.shape
        pairs = {"attended": 0, "computed": 0}
        for windowed in map(bool, window_layout):
            w = window if windowed else None
            pairs["attended"] += attended_pairs(t, w)
            pairs["computed"] += pairs_computed(t, block_k=block_k, window=w)
        for kind, count in pairs.items():
            registry.gauge(
                "autodist_swa_pairs_per_step",
                "pairs of query and key a step's attention is asked for "
                "(forward, over layers, heads and the batch: a window "
                "layer's sum_t min(t + 1, window), a global layer's causal "
                "triangle), and pairs whose score its kernels form (the "
                "tiles their loops visit, whole)", {"kind": kind}).set(
                count * batch * num_heads)

    return routed_decoder(
        name="swa_moe_lm", init=init,
        halves_of=lambda i: kinds[bool(window_layout[i]),
                                  bool(rope_layout[i])],
        kept_names=KEPT_NAMES, set_pairs_gauges=set_pairs_gauges,
        vocab_size=vocab_size, num_layers=num_layers, seq_len=seq_len,
        moe_slice=moe_slice, top_k=top_k, num_experts=num_experts,
        rms_eps=rms_eps, xent_chunk=xent_chunk, remat=remat,
        return_counts=return_counts,
        router_reads_input=router_before_attention,
        embed_scale=embed_scale,
        config=dict(vocab_size=vocab_size, num_layers=num_layers,
                    d_model=d_model, num_heads=num_heads,
                    num_kv_heads=num_kv_heads, head_dim=head_dim,
                    window=window, window_layout=tuple(window_layout),
                    rope_layout=tuple(rope_layout), d_expert=d_expert,
                    num_experts=num_experts, experts_held=held, top_k=top_k,
                    seq_len=seq_len, embed_scale=embed_scale),
    )
