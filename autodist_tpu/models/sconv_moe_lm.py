"""Decoder LM whose layers mix tokens in two ways BY A LIST: ``conv`` layers
have neither attention nor a state, only a gated short causal convolution;
``full_attention`` layers are grouped-query softmax attention.  The first
``num_dense_layers`` layers end in a dense SwiGLU, the others in
sigmoid-routed experts with a selection bias and none shared.

LFM2-MoE (``model_type: lfm2_moe``).  ``RMS(x; w) = x / rms(x) * w`` (a
plain weight); layer ``l`` of ``layer_types``:

    h = x + Op_l(RMS(x; w_op))            y = h + FFN_l(RMS(h; w_ffn))

``conv`` (kernel ``K``, no bias, no activation), ``u = RMS(x)``:

    [B C X] = u W_in          three chunks of ``D`` columns, in that order
    z = B * X
    c_t = sum_{i<K} w[:, i] z_{t-K+1+i}      depthwise over the ``D``
                              channels, causal, zeros before the sequence
    Op = (C * c) W_out

``full_attention`` (``H`` query heads over ``G`` key/value heads of ``Dh``):

    q = u W_q;  k = u W_k;  v = u W_v        no bias
    q, k = RMS over Dh, then rotary over ALL of a head's columns (halves
           against each other)
    a = causal softmax attention, scores / sqrt(Dh); query head ``j`` reads
        key/value head ``j // (H // G)`` (the flash kernel, HLO name
        ``gqa_attn``)
    Op = concat(a) W_o

FFN, ``l < num_dense_layers``: ``(silu(m W_1) * (m W_3)) W_2`` at ``d_ff``.
FFN, the others: ``parallel/moe.py: routed_moe_ffn(scoring="sigmoid",
norm_eps=1e-6)``: ``s = sigmoid(m W_r)``, the ``top_k`` largest of ``s +
b`` (``b`` takes no gradient), ``g_e = routed_scale * s_e / (sum_S s +
1e-6)``; ``experts_held`` is this chip's share.  The head multiplies by the
embedding table where ``tie_embedding`` (the family's convention).

Built on ``routed_decoder.routed_decoder`` (the halves under their
checkpoints, the slices, the loss, the gauges, the batch): the mixers one
sequence at a time, the experts once over the step's tokens, the dense FFN
one slice at a time.  Kept by name over the layers' checkpoints: the flash
kernel's ``o`` and ``lse`` and the routing integers; a ``conv`` layer keeps
nothing but its input, and its backward recomputes ``W_in``'s product, the
gates and the taps.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from autodist_tpu.models.base import ModelSpec, rms_norm, rotary_halves
from autodist_tpu.models.gdn_moe_lm import causal_conv
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
    swiglu,
)
from autodist_tpu.telemetry import registry, timeline

KEPT_NAMES = RESIDUAL_NAMES + ROUTING_RESIDUAL_NAMES
LAYER_TYPES = ("conv", "full_attention")


def gated_short_conv(bcx, taps):
    """``C * conv(B * X)``: ``bcx [B, T, 3 D]`` (the chunks ``B``, ``C``,
    ``X`` side by side), ``taps [D, K]``."""
    b, c, x = jnp.split(bcx, 3, axis=-1)
    return c * causal_conv(b * x, taps)


def sconv_moe_lm(vocab_size: int = 16384,
                 layer_types: Sequence[str] = (
                     "conv", "full_attention", "conv", "conv", "conv"),
                 num_dense_layers: int = 1, d_model: int = 2048,
                 conv_kernel: int = 3, num_heads: int = 32,
                 num_kv_heads: int = 8, head_dim: int = 64,
                 d_ff: int = 7168, d_expert: int = 1792,
                 num_experts: int = 32,
                 experts_held: Optional[Tuple[int, int]] = None,
                 top_k: int = 4, routed_scale: float = 1.0,
                 rope_theta: float = 1e6, rms_eps: float = 1e-5,
                 seq_len: int = 8192, attn_fn: Optional[Callable] = None,
                 block_k: int = _DEFAULT_BLOCK, moe_slice: int = 4096,
                 dtype=jnp.float32, xent_chunk: Optional[int] = None,
                 remat: str = "full", train_router: bool = True,
                 tie_embedding: bool = True, embed_scale: float = 1.0,
                 return_counts: bool = False) -> ModelSpec:
    """Defaults: one chip's share of LFM2-8B-A1B cut to a leading dense
    layer and one period of four (``benchmark/configs/lfm2-8b-a1b.ep4-share
    .json`` passes ``experts_held=[0, 8]``); shrink every size for tests.

    ``layer_types[l]``: ``"conv"`` or ``"full_attention"`` (the source's
    list, one entry a layer); layers ``0 .. num_dense_layers - 1`` end in
    the dense FFN.  ``attn_fn(q, k, v, True)`` as ``gqa_dsa_moe_lm``.
    ``tie_embedding``: no ``head`` leaf.  ``embed_scale``: as
    ``swa_moe_lm`` (1: the model as published).  ``remat``,
    ``experts_held``, ``xent_chunk``, ``train_router``, ``return_counts``,
    ``moe_slice``: as ``gqa_dsa_moe_lm``."""
    if num_heads % num_kv_heads:
        raise ValueError(f"{num_heads} query heads over {num_kv_heads}")
    layer_types = tuple(layer_types)
    if set(layer_types) - set(LAYER_TYPES):
        raise ValueError(f"layer_types={layer_types}: expected entries of "
                         f"{LAYER_TYPES}")
    if not 0 <= num_dense_layers < len(layer_types):
        raise ValueError(f"{num_dense_layers} dense layers of "
                         f"{len(layer_types)}")
    attn_fn = attn_fn or default_sparse_attention(block_k)
    held = tuple(experts_held) if experts_held else (0, num_experts)
    num_layers = len(layer_types)

    def init(rng):
        def normal(key, *shape):
            return jax.random.normal(key, shape, dtype) * 0.02

        def scale(width):
            return {"scale": jnp.ones((width,), dtype)}

        r_emb, r_head, r_layers = jax.random.split(rng, 3)
        params = {"embed": normal(r_emb, vocab_size, d_model),
                  "ln_final": scale(d_model)}
        if not tie_embedding:
            params["head"] = normal(r_head, vocab_size, d_model)
        for i, r in enumerate(jax.random.split(r_layers, num_layers)):
            k = jax.random.split(r, 7)
            if layer_types[i] == "conv":
                mixer = {"conv": {
                    "w_in": normal(k[0], d_model, 3 * d_model),
                    "taps": normal(k[1], d_model, conv_kernel),
                    "w_out": normal(k[2], d_model, d_model)}}
            else:
                mixer = {"attn": {
                    "wq": normal(k[0], d_model, num_heads, head_dim),
                    "wk": normal(k[1], d_model, num_kv_heads, head_dim),
                    "wv": normal(k[2], d_model, num_kv_heads, head_dim),
                    "q_norm": scale(head_dim),
                    "k_norm": scale(head_dim),
                    "wo": normal(k[3], num_heads, head_dim, d_model)}}
            if i < num_dense_layers:
                ffn = {"ffn": {"w_gate": normal(k[4], d_model, d_ff),
                               "w_up": normal(k[5], d_model, d_ff),
                               "w_down": normal(k[6], d_ff, d_model)}}
            else:
                ffn = {"moe": init_routed_moe_params(
                    k[4], d_model, d_expert, num_experts,
                    experts_held=held[1], dtype=dtype)}
            params[f"layers_{i}"] = {"ln_op": scale(d_model), **mixer,
                                     "ln_ffn": scale(d_model), **ffn}
        return params

    def conv_half(lp, x):
        """``x [B, T, D]`` plus its gated short convolution."""
        h = rms_norm(x, lp["ln_op"]["scale"], rms_eps)
        p = lp["conv"]
        with jax.named_scope(timeline.SCOPE_SCONV_PROJECT):
            bcx = h @ p["w_in"]
        with jax.named_scope(timeline.SCOPE_SCONV_CONV):
            y = gated_short_conv(bcx, p["taps"])
        with jax.named_scope(timeline.SCOPE_SCONV_PROJECT):
            return x + y @ p["w_out"]

    def attention_half(lp, x):
        """``x [B, T, D]`` plus its attention."""
        h = rms_norm(x, lp["ln_op"]["scale"], rms_eps)
        p = lp["attn"]
        with jax.named_scope(timeline.SCOPE_GQA_PROJECT):
            q, k = (rotary_halves(rms_norm(
                jnp.einsum("btd,dhk->bthk", h, p[w]), p[n]["scale"],
                rms_eps), rope_theta)
                for w, n in (("wq", "q_norm"), ("wk", "k_norm")))
            v = jnp.einsum("btd,dhk->bthk", h, p["wv"])
        # the kernel's HLO name is the innermost scope
        with jax.named_scope(timeline.SCOPE_GQA_ATTENTION), \
                jax.named_scope("gqa_attn"):
            o = attn_fn(q, k, v, True)
        with jax.named_scope(timeline.SCOPE_GQA_PROJECT):
            return x + jnp.einsum("bthv,hvd->btd", o, p["wo"])

    def dense_half(lp, x):
        """``x [1, slice, D]`` plus its dense SwiGLU."""
        with jax.named_scope(timeline.SCOPE_FFN_DENSE):
            return x + swiglu(lp["ffn"], rms_norm(
                x, lp["ln_ffn"]["scale"], rms_eps))

    def expert_half(lp, x):
        """``x [slices, slice, D]`` plus its experts' output, and the
        tokens each held expert was sent."""
        y, counts = routed_moe_ffn(
            lp["moe"], rms_norm(x, lp["ln_ffn"]["scale"], rms_eps),
            top_k=top_k, experts_held=held, routed_scale=routed_scale,
            train_router=train_router, scoring="sigmoid", norm_eps=1e-6)
        return x + y, counts

    mixers = dict(zip(LAYER_TYPES, (conv_half, attention_half)))
    attention_layers = layer_types.count("full_attention")

    def set_pairs_gauges(tokens):
        batch, t = tokens.shape
        for kind, pairs in (("causal", t * (t + 1) // 2),
                            ("computed", pairs_computed(t, block_k=block_k))):
            registry.gauge(
                "autodist_gqa_pairs_per_step",
                "pairs of query and key a step's plain grouped-query "
                "attention is asked for (forward, over layers, heads and "
                "the batch: the causal triangle), and pairs whose score "
                "its kernel forms (the tiles its loop visits, whole)",
                {"kind": kind}).set(
                pairs * batch * num_heads * attention_layers)

    return routed_decoder(
        name="sconv_moe_lm", init=init,
        halves_of=lambda i: (mixers[layer_types[i]],
                             dense_half if i < num_dense_layers
                             else expert_half),
        kept_names=KEPT_NAMES, set_pairs_gauges=set_pairs_gauges,
        vocab_size=vocab_size, num_layers=num_layers, seq_len=seq_len,
        moe_slice=moe_slice, top_k=top_k, num_experts=num_experts,
        rms_eps=rms_eps, xent_chunk=xent_chunk, remat=remat,
        return_counts=return_counts, embed_scale=embed_scale,
        dense_layers=tuple(range(num_dense_layers)),
        tie_head=tie_embedding,
        config=dict(vocab_size=vocab_size, num_layers=num_layers,
                    layer_types=layer_types,
                    num_dense_layers=num_dense_layers,
                    d_model=d_model, conv_kernel=conv_kernel,
                    num_heads=num_heads, num_kv_heads=num_kv_heads,
                    head_dim=head_dim, d_ff=d_ff, d_expert=d_expert,
                    num_experts=num_experts, experts_held=held, top_k=top_k,
                    tie_embedding=tie_embedding, embed_scale=embed_scale,
                    seq_len=seq_len),
    )
