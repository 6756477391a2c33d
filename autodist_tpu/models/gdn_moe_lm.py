"""Decoder LM whose layers mix tokens in two ways by index: LINEAR layers
carry a recurrent state from token to token (Gated DeltaNet), every
``full_interval``-th layer is softmax attention with an output gate; every
layer ends in softmax-routed experts beside a gated shared expert.

Qwen3-Next (``model_type: qwen3_next``).  ``RMS0(x; w) = x / rms(x) * (1 +
w)`` is the model's zero-centred norm (``rms_norm`` handed ``1 + w``);
layer ``i`` is FULL where ``(i + 1) % full_interval == 0``:

    u = x + Mixer(RMS0(x; w_in))          x' = u + MoE(RMS0(u; w_post))

LINEAR mixer (``Hk`` key heads and ``Hv = r Hk`` value heads of ``Dl``,
value heads ``r j .. r j + r - 1`` read key head ``j``), ``h = RMS0(x)``:

    [q k v z] = h W_qkvz      by key head: Dl of q, Dl of k, r Dl of v, of z
    [b a]     = h W_ba        by key head: r of b, r of a
    c_t = silu(sum_{i<K} w_conv[:, i] * [q k v]_{t-K+1+i})    depthwise over
                              the channels of q | k | v, causal, no bias
    q = l2norm(c_q) / sqrt(Dl), k = l2norm(c_k), v = c_v
    beta = sigmoid(b)    g = -exp(A_log) softplus(a + dt_bias)    float32
    o = gated_delta_rule(q, k, v, g, beta)      ops/gated_delta_rule.py
    y = (rmsnorm(o; w_n) * silu(z)) W_out       the norm over a head's Dl

The two lines of ``c`` and of q, k, v with the split of ``qkvz`` are ONE
pair of Pallas kernels on a TPU (``ops/gdn_conv.py: conv_silu_l2norm``, HLO
names ``gdn_conv`` and ``gdn_conv_bwd``, under the scope ``gdn/conv``,
l2norm with them: PR 48) and :func:`conv_qkvz`, the same lines in
``jax.numpy``, elsewhere.

FULL mixer (``H`` query heads over ``G`` key/value heads of ``Dh``):

    [q gate] = h W_q  by head;  k = h W_k;  v = h W_v
    q, k = RMS0 over Dh, then rotary on the FIRST ``rotary_dim`` columns of
           every head (halves of those against each other)
    a = causal softmax attention (the flash kernel, HLO name ``gated_attn``)
    y = (concat(a) * sigmoid(gate)) W_o

MoE: ``parallel/moe.py: routed_moe_ffn(scoring="softmax_of_picked")`` with
a ``shared_gate`` leaf: ``sigmoid(m w_sg) * Shared(m)`` beside the picks.

What stands in the leaves.  The benchmark draws every leaf not named
``scale`` normal(0, 0.02) and sets those named ``scale`` to 1
(``benchmark/weights.py``), so: a zero-centred norm's leaf is ``w``; the
gated norm's plain weight and ``dt_bias`` are leaves named ``scale`` (1, the
public implementation's initial value); and ``A_log = a_log +
decay_offsets(Hv)``: the leaf is what training moves, the constant puts
head ``h`` at the ``(h + 1/2) / Hv`` quantile of the ``log U(0, 16)`` that
implementation draws ``A_log`` from, so that the heads forget at rates from
0.25 to 15.75 (times ``softplus``) and not all at one.

Built on ``routed_decoder.routed_decoder`` (the halves under their
checkpoints, the slices, the loss, the gauges, the batch); the mixers one
sequence at a time, the experts once over the step's tokens.  Kept by
name over the layers' checkpoints: the flash kernel's ``o`` and ``lse``,
the routing integers, and the recurrence's ``o`` and the states its
segments are entered with (``gated_delta_rule.RESIDUAL_NAMES``: 134 MB
and 34 MB a sequence and layer at the published widths): with them the
backward's recomputation of a linear mixer stops at the projections and
the convolution and never runs the rule's forward kernel a second time.

What of a linear mixer still crosses main memory more often than it must
(``ROADMAP.md`` S16, in the order of their ms a step in the qwen3-next
cell, ``PERF.md`` section 5): the gated norm times ``silu(z)`` a pass of
its own before ``W_out``, and the weights' gradient sums ``fusion
f32[8192]``.  The convolution, l2norm and the copy of z out of ``qkvz``'s
columns left that list with PR 48; the recurrence's state-free blocks
(``_prepare`` formed twice around the scan kernels) with PR 49: its two
kernels form them in VMEM from q, k, v, ``G`` and ``beta``.
"""
from __future__ import annotations

import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from autodist_tpu.models.base import ModelSpec, rms_norm, rotary_halves
from autodist_tpu.models.routed_decoder import routed_decoder
from autodist_tpu.models.transformer import default_sparse_attention
from autodist_tpu.ops import gdn_conv
from autodist_tpu.ops.flash_attention import _DEFAULT_BLOCK, RESIDUAL_NAMES
from autodist_tpu.ops.gated_delta_rule import (
    RESIDUAL_NAMES as GDN_RESIDUAL_NAMES,
    flops_per_token,
    gated_delta_rule,
)
from autodist_tpu.parallel.moe import (
    ROUTING_RESIDUAL_NAMES,
    init_routed_moe_params,
    routed_moe_ffn,
)
from autodist_tpu.telemetry import registry, timeline

KEPT_NAMES = RESIDUAL_NAMES + GDN_RESIDUAL_NAMES + ROUTING_RESIDUAL_NAMES


def decay_offsets(heads: int) -> jax.Array:
    """``log`` of the ``(h + 1/2) / heads`` quantiles of U(0, 16)."""
    return jnp.log(16.0 * (jnp.arange(heads, dtype=jnp.float32) + 0.5)
                   / heads)


def l2norm(x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, -1, keepdims=True)
                                + eps)).astype(x.dtype)


def causal_conv(x, w):
    """``y_t = sum_i w[.., i] x_{t-K+1+i}`` along axis 1 of ``x [B, T,
    ..]`` (zeros before the sequence), ``w [.., K]`` a channel: ``K``
    shifted multiply-adds."""
    taps, t = w.shape[-1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0)) + ((0, 0),) * (x.ndim - 2))
    return sum(jax.lax.slice_in_dim(padded, i, i + t, axis=1) * w[..., i]
               for i in range(taps))


def conv_qkvz(qkvz, w_q, w_k, w_v, scale: float):
    """``(q, k [B, T, Hk, Dl], v, z [B, T, r Hk, Dl])`` of ``qkvz [B, T, Hk,
    (2 + 2 r) Dl]``: q, k, v convolved under their taps (``w_q, w_k [Hk, Dl,
    K]``, ``w_v [Hk, r Dl, K]``) and through silu, q and k l2-normed, q
    times ``scale``, z as it is.  The plain form: what runs off a TPU, and
    what ``ops/gdn_conv.py: conv_silu_l2norm`` is held to
    (``tests/test_gdn_conv.py``)."""
    dl = w_q.shape[1]
    q, k, v, z = jnp.split(qkvz, (dl, 2 * dl, 2 * dl + w_v.shape[1]),
                           axis=-1)
    q, k, v = (jax.nn.silu(causal_conv(y, w))
               for y, w in ((q, w_q), (k, w_k), (v, w_v)))
    heads = q.shape[:2] + (-1, dl)
    return (l2norm(q) * scale, l2norm(k), v.reshape(heads),
            z.reshape(heads))


def partial_rotary(x, theta: float, columns: int):
    """The rotary on the first ``columns`` of every head's last axis."""
    if columns == x.shape[-1]:
        return rotary_halves(x, theta)
    return jnp.concatenate([rotary_halves(x[..., :columns], theta),
                            x[..., columns:]], axis=-1)


def gdn_moe_lm(vocab_size: int = 18992, num_layers: int = 4,
               d_model: int = 2048, full_interval: int = 4,
               linear_key_heads: int = 16, linear_value_heads: int = 32,
               linear_head_dim: int = 128, conv_kernel: int = 4,
               num_heads: int = 16, num_kv_heads: int = 2,
               head_dim: int = 256, rotary_dim: int = 64,
               d_expert: int = 512, d_shared: int = 512,
               num_experts: int = 512,
               experts_held: Optional[Tuple[int, int]] = None,
               top_k: int = 10, rope_theta: float = 1e7,
               rms_eps: float = 1e-6, seq_len: int = 8192,
               attn_fn: Optional[Callable] = None,
               gdn_fn: Optional[Callable] = None, chunk: int = 64,
               block_k: int = _DEFAULT_BLOCK, moe_slice: int = 4096,
               dtype=jnp.float32, xent_chunk: Optional[int] = None,
               remat: str = "full", train_router: bool = True,
               return_counts: bool = False) -> ModelSpec:
    """Defaults: one chip's share of Qwen3-Next-80B-A3B-Instruct cut to
    its first period of four layers (``benchmark/configs/qwen3-next-80b
    -a3b.ep16-share.json`` passes ``experts_held=[0, 32]``); shrink every
    size for tests.

    ``gdn_fn(q, k, v, g, beta)``: the recurrence (default
    ``gated_delta_rule`` at ``chunk``: its kernels on a TPU, the plain
    form elsewhere).  ``attn_fn(q, k, v, True)`` as ``gqa_dsa_moe_lm``.
    ``remat``, ``experts_held``, ``xent_chunk``, ``train_router``,
    ``return_counts``, ``moe_slice``: as ``gqa_dsa_moe_lm``."""
    if num_heads % num_kv_heads or linear_value_heads % linear_key_heads:
        raise ValueError(f"{num_heads} query heads over {num_kv_heads}, "
                         f"{linear_value_heads} value heads over "
                         f"{linear_key_heads} key heads")
    attn_fn = attn_fn or default_sparse_attention(block_k)
    if gdn_fn is None:
        def gdn_fn(*operands):
            return gated_delta_rule(*operands, chunk=chunk)
    held = tuple(experts_held) if experts_held else (0, num_experts)
    hk, dl = linear_key_heads, linear_head_dim
    share = linear_value_heads // hk            # value heads a key head
    full = [(i + 1) % full_interval == 0 for i in range(num_layers)]

    def init(rng):
        def normal(key, *shape):
            return jax.random.normal(key, shape, dtype) * 0.02

        def centred(width):
            return {"w": jnp.zeros((width,), dtype)}

        def ones(width):
            return {"scale": jnp.ones((width,), dtype)}

        r_emb, r_head, r_layers = jax.random.split(rng, 3)
        params = {"embed": normal(r_emb, vocab_size, d_model),
                  "head": normal(r_head, vocab_size, d_model),
                  "ln_final": centred(d_model)}
        for i, r in enumerate(jax.random.split(r_layers, num_layers)):
            k = jax.random.split(r, 5)
            if full[i]:
                mixer = {"attn": {
                    "wq": normal(k[0], d_model, num_heads, 2 * head_dim),
                    "wk": normal(k[1], d_model, num_kv_heads, head_dim),
                    "wv": normal(k[2], d_model, num_kv_heads, head_dim),
                    "q_norm": centred(head_dim),
                    "k_norm": centred(head_dim),
                    "wo": normal(k[3], num_heads, head_dim, d_model)}}
            else:
                mixer = {"gdn": {
                    "w_qkvz": normal(k[0], d_model, hk,
                                     2 * dl * (1 + share)),
                    "w_ba": normal(k[1], d_model, hk, 2 * share),
                    "conv": normal(k[2], hk * dl * (2 + share), conv_kernel),
                    "a_log": jnp.zeros((hk * share,), dtype),
                    "dt_bias": ones(hk * share),
                    "norm": ones(dl),
                    "w_out": normal(k[3], hk * share, dl, d_model)}}
            params[f"layers_{i}"] = {
                "ln_in": centred(d_model), **mixer,
                "ln_post": centred(d_model),
                "moe": init_routed_moe_params(
                    k[4], d_model, d_expert, num_experts,
                    experts_held=held[1], d_shared=d_shared,
                    selection_bias=False, shared_gate=True, dtype=dtype)}
        return params

    def rms0(x, p):
        return rms_norm(x, 1.0 + p["w"], rms_eps)

    def linear_half(lp, x):
        """``x [B, T, D]`` plus its Gated DeltaNet mixer."""
        b, t = x.shape[:2]
        h = rms0(x, lp["ln_in"])
        p = lp["gdn"]
        with jax.named_scope(timeline.SCOPE_GDN_PROJECT):
            qkvz = jnp.einsum("btd,dhc->bthc", h, p["w_qkvz"])
            ba = jnp.einsum("btd,dhc->bthc", h, p["w_ba"])
        with jax.named_scope(timeline.SCOPE_GDN_CONV):
            taps = (w.reshape(hk, -1, conv_kernel) for w in
                    jnp.split(p["conv"], (hk * dl, 2 * hk * dl)))
            q, k, v, z = (conv_qkvz if gdn_conv._use_interpret() else
                          gdn_conv.conv_silu_l2norm)(
                qkvz, *taps, 1.0 / math.sqrt(dl))
        with jax.named_scope(timeline.SCOPE_GDN_RECURRENCE):
            beta = jax.nn.sigmoid(ba[..., :share].astype(jnp.float32))
            g = -jnp.exp(p["a_log"].astype(jnp.float32)
                         + decay_offsets(hk * share)) * jax.nn.softplus(
                ba[..., share:].astype(jnp.float32).reshape(b, t, -1)
                + p["dt_bias"]["scale"].astype(jnp.float32))
            o = gdn_fn(q, k, v, g, beta.reshape(b, t, -1))
        with jax.named_scope(timeline.SCOPE_GDN_PROJECT):
            y = rms_norm(o, p["norm"]["scale"], rms_eps) * jax.nn.silu(z)
            return x + jnp.einsum("bthv,hvd->btd", y, p["w_out"])

    def full_half(lp, x):
        """``x [B, T, D]`` plus its gated attention."""
        h = rms0(x, lp["ln_in"])
        p = lp["attn"]
        with jax.named_scope(timeline.SCOPE_GQA_PROJECT):
            q, gate = jnp.split(jnp.einsum("btd,dhc->bthc", h, p["wq"]), 2,
                                axis=-1)
            k, v = (jnp.einsum("btd,dhk->bthk", h, p[w])
                    for w in ("wk", "wv"))
            q, k = (partial_rotary(rms0(y, p[n]), rope_theta, rotary_dim)
                    for y, n in ((q, "q_norm"), (k, "k_norm")))
        # the kernel's HLO name is the innermost scope
        with jax.named_scope(timeline.SCOPE_GATTN_ATTENTION), \
                jax.named_scope("gated_attn"):
            o = attn_fn(q, k, v, True)
        with jax.named_scope(timeline.SCOPE_GQA_PROJECT):
            return x + jnp.einsum("bthv,hvd->btd", o * jax.nn.sigmoid(gate),
                                  p["wo"])

    def expert_half(lp, x):
        """``x [slices, slice, D]`` plus its experts' output, and the
        tokens each held expert was sent."""
        y, counts = routed_moe_ffn(
            lp["moe"], rms0(x, lp["ln_post"]), top_k=top_k,
            experts_held=held, train_router=train_router,
            scoring="softmax_of_picked")
        return x + y, counts

    kinds = {True: (full_half, expert_half),
             False: (linear_half, expert_half)}

    def set_flops_gauges(tokens):
        per_token = flops_per_token(dl, dl, chunk)
        times = tokens.size * hk * share * (num_layers - sum(full))
        for kind, count in per_token.items():
            registry.gauge(
                "autodist_gdn_flops_per_step",
                "FLOPs of a step's gated delta rule, forward, over layers, "
                "value heads and the batch: the recurrence as written (7 a "
                "state element a token) and what the chunked form at the "
                "model's chunk performs", {"kind": kind}).set(count * times)

    return routed_decoder(
        name="gdn_moe_lm", init=init, halves_of=lambda i: kinds[full[i]],
        kept_names=KEPT_NAMES, set_pairs_gauges=set_flops_gauges,
        vocab_size=vocab_size, num_layers=num_layers, seq_len=seq_len,
        moe_slice=moe_slice, top_k=top_k, num_experts=num_experts,
        rms_eps=rms_eps, xent_chunk=xent_chunk, remat=remat,
        return_counts=return_counts,
        final_scale=lambda p: 1.0 + p["w"],
        config=dict(vocab_size=vocab_size, num_layers=num_layers,
                    d_model=d_model, full_interval=full_interval,
                    linear_key_heads=hk,
                    linear_value_heads=hk * share, linear_head_dim=dl,
                    conv_kernel=conv_kernel, num_heads=num_heads,
                    num_kv_heads=num_kv_heads, head_dim=head_dim,
                    rotary_dim=rotary_dim, d_expert=d_expert,
                    d_shared=d_shared, num_experts=num_experts,
                    experts_held=held, top_k=top_k, chunk=chunk,
                    seq_len=seq_len),
    )
