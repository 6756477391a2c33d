"""Decoder LM with grouped-query heads that attend only to the keys a
learned indexer selects, and softmax-routed experts.

The language model of Keye-VL-2.0 (``model_type: KeyeVL2``): a Qwen3-MoE
block whose attention is DeepSeek sparse attention (DeepSeek-AI 2025, the
DeepSeek-V3.2-Exp report).  One layer, ``h = RMSNorm(x)``:

* **Main heads.**  ``q = h W_q [T, H, Dh]``, ``k = h W_k``, ``v = h W_v``
  ``[T, G, Dh]``, no bias; RMSNorm over ``Dh`` of every query and key
  head, then rotary on half-split pairs (``rotary_halves``: what the
  weights' columns mean here).  Query head ``j`` reads key/value head
  ``j // (H // G)``; ``ops/flash_attention.py`` repeats the index, never
  the keys.
* **Indexer.**  ``qI = h W_qI [T, J, Di]``, ``kI = LayerNorm(h W_kI)
  [T, Di]``, ``w = h W_w [T, J]``, rotary on both;
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``, in
  float32 at ``highest`` so that the selection does not turn on rounding.
  ``S_t``: the ``topk`` largest of row ``t`` (all of ``0..t`` before row
  ``topk``), ties to the lower position, one bit a pair
  (``pack_selection``'s words).  Two forms of one selection, chosen by the
  platform as every kernel of the repo is.  On a TPU
  ``ops/index_select.py: dsa_select``, ONE kernel a layer and sequence: a
  tile of 256 queries' scores against the keys at or before its last row
  are formed, searched for each row's threshold and packed in VMEM, and
  only the words leave (the six bfloat16 products of ``highest`` two to a
  128-deep pass).  Elsewhere, and as what the tests hold the kernel to,
  the plain form :func:`select_keys`: ``index_rows`` queries at a time,
  :func:`index_scores`, ``ops/topk_select.py`` for each row's threshold
  without sorting, ``pack_selection``.  Rows that select everything form
  no score.  The cross-entropy has no path into the indexer (its own
  alignment loss is not written): its leaves get a zero gradient.
* **Attention** over ``S_t`` alone, by a mask inside the flash kernel:
  every causal tile is computed (``autodist_dsa_pairs_per_step`` says how
  many pairs that is beside the pairs selected).
* **Experts** (``parallel/moe.py: routed_moe_ffn``, ``scoring="softmax"``):
  top-``k`` of a softmax over all experts, weights renormalised over the
  picks, no bias, none shared, no token dropped; ``experts_held`` is this
  chip's share.  One call a layer over all of a step's tokens, handed in
  as slices of ``moe_slice`` tokens: no buffer of the layer is wider than
  a slice's ``slice * k`` picks.

Built on ``models/routed_decoder.py``; functional, the training path only.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from autodist_tpu.models.base import ModelSpec, rms_norm, rotary_halves
from autodist_tpu.models.routed_decoder import routed_decoder
from autodist_tpu.models.transformer import default_sparse_attention
from autodist_tpu.ops.flash_attention import (
    _DEFAULT_BLOCK,
    RESIDUAL_NAMES,
    pack_selection,
    pairs_computed,
)
from autodist_tpu.ops import index_select
from autodist_tpu.ops.pallas_utils import pick_block
from autodist_tpu.ops.topk_select import top_k_mask
from autodist_tpu.parallel.moe import (
    ROUTING_RESIDUAL_NAMES,
    init_routed_moe_params,
    routed_moe_ffn,
)
from autodist_tpu.telemetry import registry, step_values, timeline

#: a layer's selection, as ``pack_selection``'s words: kept by name, so
#: the backward neither scores nor selects again
SELECTION_NAME = "dsa/selection"
KEPT_NAMES = RESIDUAL_NAMES + (SELECTION_NAME,) + ROUTING_RESIDUAL_NAMES


def layer_norm(x, p, eps):
    x32 = x.astype(jnp.float32)
    x32 = x32 - x32.mean(-1, keepdims=True)
    out = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (out * p["scale"] + p["bias"]).astype(x.dtype)


def index_scores(qi, ki, w):
    """``I [rows, keys] = sum_j w[:, j] relu(qI[:, j] . kI)``: ``qi [rows,
    J, Di]``, ``ki [keys, Di]``, ``w [rows, J]``, one head at a time (all
    ``J`` at once are ``J`` times the result), float32 at ``highest``."""
    def head(acc, qw):
        q_j, w_j = qw
        s = jnp.dot(q_j, ki.T, precision=jax.lax.Precision.HIGHEST)
        return acc + w_j[:, None] * jax.nn.relu(s), None

    acc = jnp.zeros((qi.shape[0], ki.shape[0]), jnp.float32)
    return jax.lax.scan(head, acc, (jnp.moveaxis(qi, 1, 0).astype(
        jnp.float32), w.T.astype(jnp.float32)))[0]


def select_keys(qi, ki, w, *, topk: int, rows: int, block_k: int):
    """The selection of one sequence as ``pack_selection``'s words ``[T //
    32, T]`` (keys down, queries along): ``qi [T, J, Di]``, ``ki [T,
    Di]``, ``w [T, J]``.  ``rows`` queries at a time, each block against
    keys ``0 .. its last row``; a block that ends at or before row
    ``topk`` selects all earlier keys and forms no score.  The plain form:
    what runs off a TPU, and what ``ops/index_select.py: dsa_select`` is
    held to bit for bit (``tests/test_index_select.py``)."""
    t = qi.shape[0]
    if t % rows or rows % block_k:
        raise ValueError(f"{t} rows in blocks of {rows} over key blocks "
                         f"of {block_k}")
    out = []
    for start in range(0, t, rows):
        keys = start + rows
        seen = jnp.arange(keys)[None, :] \
            <= (start + jnp.arange(rows))[:, None]
        if keys <= topk:
            picked = seen
        else:
            with jax.named_scope(timeline.SCOPE_DSA_INDEX):
                scores = index_scores(qi[start:keys], ki[:keys],
                                      w[start:keys])
            with jax.named_scope(timeline.SCOPE_DSA_SELECT):
                # + 0.0: the one zero a row of relus can produce twice
                picked = top_k_mask(scores + 0.0, topk, seen)
        with jax.named_scope(timeline.SCOPE_DSA_SELECT):
            words = pack_selection(picked, block_k=block_k)
            out.append(jnp.pad(words, ((0, (t - keys) // 32), (0, 0))))
    return jnp.concatenate(out, axis=1)


def gqa_dsa_moe_lm(vocab_size: int = 18992, num_layers: int = 4,
                   d_model: int = 2048, num_heads: int = 32,
                   num_kv_heads: int = 4, head_dim: int = 128,
                   index_heads: int = 16, index_dim: int = 64,
                   topk: int = 2048, d_expert: int = 768,
                   num_experts: int = 128,
                   experts_held: Optional[Tuple[int, int]] = None,
                   top_k: int = 8, rope_theta: float = 1e7,
                   rms_eps: float = 1e-6, seq_len: int = 16384,
                   attn_fn: Optional[Callable] = None,
                   block_k: int = _DEFAULT_BLOCK, index_rows: int = 1024,
                   moe_slice: int = 4096, dtype=jnp.float32,
                   xent_chunk: Optional[int] = None, remat: str = "full",
                   train_router: bool = True,
                   return_counts: bool = False) -> ModelSpec:
    """Defaults: one chip's share of Keye-VL-2.0-30B-A3B's language model
    cut to four layers (``benchmark/configs/keye-vl-2.0-30b-a3b.ep8-share
    .json`` passes ``experts_held=[0, 16]``); shrink every size for tests.

    ``attn_fn(q, k, v, True, selection=words, select_from=topk)`` (no
    keywords where the sequence is no longer than ``topk``); ``block_k``
    is the key block the words are packed for, the attention's own.
    ``index_rows``: the queries of a block of the PLAIN form of the
    selection (:func:`select_keys`, off a TPU); the kernel that selects on
    a TPU takes its tile from the shapes (``index_select.tile_of``).
    ``remat``: "none" | "full": the attention half and the expert half of
    a layer are recomputed in the backward EXCEPT what a kernel, a
    selection or a sort produced (``KEPT_NAMES``).  ``moe_slice``: no
    buffer of the expert layer is wider than the picks of this many
    tokens (``routed_moe_ffn``'s chunk).
    ``experts_held``, ``xent_chunk``, ``train_router``, ``return_counts``:
    as ``mla_moe_lm``."""
    if num_heads % num_kv_heads:
        raise ValueError(f"{num_heads} query heads over {num_kv_heads}")
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
            k = jax.random.split(r, 8)
            params[f"layers_{i}"] = {
                "ln_attn": scale(d_model),
                "attn": {"wq": normal(k[0], d_model, num_heads, head_dim),
                         "wk": normal(k[1], d_model, num_kv_heads, head_dim),
                         "wv": normal(k[2], d_model, num_kv_heads, head_dim),
                         "q_norm": scale(head_dim),
                         "k_norm": scale(head_dim),
                         "wo": normal(k[3], num_heads, head_dim, d_model)},
                "indexer": {"wq": normal(k[4], d_model, index_heads,
                                         index_dim),
                            "wk": normal(k[5], d_model, index_dim),
                            "k_norm": dict(scale(index_dim),
                                           bias=jnp.zeros((index_dim,),
                                                          dtype)),
                            "weights": normal(k[6], d_model, index_heads)},
                "ln_mlp": scale(d_model),
                "moe": init_routed_moe_params(
                    k[7], d_model, d_expert, num_experts,
                    experts_held=held[1], selection_bias=False, dtype=dtype)}
        return params

    def plain_blocks(t):
        """(the key block the words are packed for, the rows of a block of
        the plain form) at ``t`` tokens."""
        bk = pick_block(t, block_k)
        return bk, max(bk, min(index_rows, t) // bk * bk)

    def selection_of(p, h):
        """``pack_selection``'s words ``[B, T // 32, T]`` of ``h [B, T,
        D]`` (the layer's normed input), or None where nothing is left
        out, and the kernel's tiles that searched a second time ``[B,
        tiles]`` (None off the TPU).  Integers: no gradient passes."""
        t = h.shape[1]
        if t <= topk:
            return None, None
        # projections and scores alike in float32 at ``highest``: a score
        # a bfloat16 pass moved by 3e-3 changes places with its neighbours
        with jax.named_scope(timeline.SCOPE_DSA_INDEX), \
                jax.default_matmul_precision("highest"):
            h = h.astype(jnp.float32)
            qi = rotary_halves(jnp.einsum("btd,djk->btjk", h, p["wq"]),
                               rope_theta)
            ki = rotary_halves(layer_norm(h @ p["wk"], p["k_norm"], rms_eps),
                               rope_theta)
            w = h @ p["weights"]
        bk, rows = plain_blocks(t)
        # a map, not a vmap: the second search stays a branch
        if index_select._use_interpret():
            words, ties = jax.lax.map(lambda row: select_keys(
                *row, topk=topk, rows=rows, block_k=bk), (qi, ki, w)), None
        else:
            # the kernel's HLO name is the innermost scope
            with jax.named_scope(timeline.SCOPE_DSA_SELECT), \
                    jax.named_scope("dsa_select"):
                words, ties = jax.lax.map(
                    lambda row: index_select.dsa_select(
                        *row, topk=topk, block_k=bk),
                    jax.lax.stop_gradient((qi, ki, w)))
        return checkpoint_name(words, SELECTION_NAME), ties

    def attention_half(lp, x):
        """``x [B, T, D]`` plus its attention."""
        h = rms_norm(x, lp["ln_attn"]["scale"], rms_eps)
        p = lp["attn"]
        with jax.named_scope(timeline.SCOPE_GQA_PROJECT):
            q, k = (rotary_halves(rms_norm(
                jnp.einsum("btd,dhk->bthk", h, p[w]), p[n]["scale"],
                rms_eps), rope_theta)
                for w, n in (("wq", "q_norm"), ("wk", "k_norm")))
            v = jnp.einsum("btd,dhk->bthk", h, p["wv"])
        selection, ties = selection_of(lp["indexer"],
                                       jax.lax.stop_gradient(h))
        # the kernel's HLO name is the innermost scope
        with jax.named_scope(timeline.SCOPE_DSA_ATTENTION), \
                jax.named_scope("sparse_attn"):
            o = attn_fn(q, k, v, True) if selection is None else attn_fn(
                q, k, v, True, selection=selection, select_from=topk)
        with jax.named_scope(timeline.SCOPE_GQA_PROJECT):
            x = x + jnp.einsum("bthv,hvd->btd", o, p["wo"])
        return x if ties is None else (x, ties)

    def expert_half(lp, x):
        """``x [slices, slice, D]`` plus its experts' output, and the
        tokens each held expert was sent."""
        y, counts = routed_moe_ffn(
            lp["moe"], rms_norm(x, lp["ln_mlp"]["scale"], rms_eps),
            top_k=top_k, experts_held=held, train_router=train_router,
            scoring="softmax")
        return x + y, counts

    def set_pairs_gauges(tokens):
        batch, t = tokens.shape
        whole = min(t, topk)       # rows that attend to all before them
        selected = whole * (whole + 1) // 2 + (t - whole) * topk
        for kind, pairs in (("selected", selected), ("computed",
                            pairs_computed(t, block_k=block_k))):
            registry.gauge(
                "autodist_dsa_pairs_per_step",
                "pairs of query and key a step's attention is asked for "
                "(a head, forward), and pairs whose score its kernel "
                "forms", {"kind": kind}).set(pairs * batch * num_layers)
        # the rows in the tiles (the kernel's) or blocks (the plain form's)
        # that end past row ``topk``: the others form no score
        plain = index_select._use_interpret()
        tile = plain_blocks(t)[1] if plain else index_select.tile_of(t)
        scored = t - topk // tile * tile if t > topk else 0
        for path, rows in (("plain", scored * plain),
                           ("kernel", scored * (not plain)),
                           ("whole", t - scored)):
            registry.gauge(
                "autodist_dsa_select_rows_per_step",
                "query rows a step's layers select keys for, by what "
                "formed their selection: the dsa_select kernel, the plain "
                "form (off a TPU), or nothing (a tile or block of rows "
                "with topk or fewer keys takes them all and forms no "
                "score)", {"path": path}).set(rows * batch * num_layers)

    def record_ties(noted):
        """``noted``: the layers' ``[B, tiles]`` flags of the kernel's
        second search, None where the plain form selected."""
        flags = [n for n in noted if n is not None]
        if not flags:
            return

        def publish(searches):      # a scalar, stacked over microbatches
            registry.gauge(
                "autodist_dsa_tie_searches_per_step",
                "tiles of queries of the last step's dsa_select calls in "
                "which some row had more keys equal to its threshold than "
                "places left, so that the search over positions ran"
            ).set(int(np.sum(searches)))

        step_values.emit("dsa_tie_searches", jnp.sum(jnp.stack(flags)),
                         publish)

    return routed_decoder(
        name="gqa_dsa_moe_lm", init=init,
        halves_of=lambda i: (attention_half, expert_half),
        kept_names=KEPT_NAMES, set_pairs_gauges=set_pairs_gauges,
        record_attention=record_ties,
        vocab_size=vocab_size, num_layers=num_layers, seq_len=seq_len,
        moe_slice=moe_slice, top_k=top_k, num_experts=num_experts,
        rms_eps=rms_eps, xent_chunk=xent_chunk, remat=remat,
        return_counts=return_counts,
        config=dict(vocab_size=vocab_size, num_layers=num_layers,
                    d_model=d_model, num_heads=num_heads,
                    num_kv_heads=num_kv_heads, head_dim=head_dim,
                    index_heads=index_heads, index_dim=index_dim, topk=topk,
                    d_expert=d_expert, num_experts=num_experts,
                    experts_held=held, top_k=top_k, seq_len=seq_len),
    )
