"""The plain language model of ``model_type: sdar_moe`` trained by diffusion
over blocks: noise, the two-copy forward pass under the block mask, the
weighted loss, gradients and AdamW in ``jax.numpy``, for the whole block
and for ONE CHIP'S SHARE of it under expert parallelism.

A Qwen3-MoE block (``reference/keye_vl2.py``'s without the indexer) under
the objective of SDAR (arXiv:2510.06303), which is that of Arriola et al.
2025, "Block Diffusion" (arXiv:2503.09573, Algorithm 1 and the training
mask of section 3.2):

    block    h = x + Attn(RMSNorm(x));  y = h + Experts(RMSNorm(h))
    heads    q_i = RMSNorm(x W_q)_i, k_g = RMSNorm(x W_k)_g (over the head's
             width), v_g = x W_v; rotary, half against half, on q and k;
             query head i reads key/value head i // (H / G)
    noise    a sequence x of L tokens in blocks of B; block b draws a level
             t_b = eps + (1 - eps) u_b; token i is masked (m_i) where its
             own uniform is below t_{i // B}; x~_i = MASK where m_i, else x_i
    rows     ids [x ; x~], 2 L of them, at positions [0..L-1 ; 0..L-1];
             the stream enters layer 0 as embed_scale times the table's rows
    mask     query r sees key c iff
               clean -> clean    c // B <= r // B
               noised -> clean   c // B < (r - L) // B
               noised -> noised  (c - L) // B == (r - L) // B
               clean -> noised   never
    Attn     o_i[r] = softmax over the keys r sees of (q_i[r] . k_g[c] /
             sqrt(Dh)) applied to v_g;  Attn = concat_i(o_i) W_o
    Experts  p = softmax(x W_r) over all E;  S = top-k of p
             g_e = p_e / sum_{j in S} p_j;  Experts = sum_{e in S} g_e E_e(x)
    end      z_i = RMSNorm(y_{L+i}) W_head over the noised half;
             loss = 1 / (rows L) sum_i (m_i / t_{i // B}) CE(z_i, x_i)

No shift: a masked row predicts its own token.  The draws of a sequence
come from ``fold_in(key(noise_seed), d)``, ``d = sum_i (x_i + 1)(2 i + 1)``
in wrapping uint32 over that sequence's own tokens, split in two: the first
key draws the ``L / B`` levels, the second one uniform a token (the
configuration's ``assumed.noise_rule``; the program writes the same rule in
its own lines, and a step's batch is handed to both as tokens and nothing
else).  Under the share (``deployment.experts_held``) the experts' sum runs
over the held ones, normalised over all of ``S``.

It imports nothing of the program and none of its mechanisms: the mask is
built from the four rules by ``//`` on index grids and applied to the
dense scores of ``ROWS`` queries at a time against all ``2 L`` keys; every
held expert is applied to every row and weighted by a mask
(``keye_vl2.experts``: the same layer), ``CHUNK`` rows at a time.
``Settings.wrong = "causal_mask"`` (``benchmark/wrong_models.py``) puts the
plain causal triangle over the 2 L rows in the rules' place: ANOTHER model,
the stand-in that a run's limits have to fail.
Weights by the program's names:

    embed, head [V, D], ln_final/scale, layers_<i>/{ln_attn/scale,
    attn/{wq [D,H,Dh], wk, wv [D,G,Dh], q_norm/scale, k_norm/scale [Dh],
    wo [H,Dh,D]}, ln_mlp/scale, moe/{router [D,E],
    experts/{w_gate,w_up [held,D,F], w_down [held,F,D]}}}

Memory at the cell's size (456 M parameters, 16,384 rows): five copies of
the weights are 9.1 GB, so the rest is kept small: a block of 64 queries
against 16,384 keys over 32 heads is 0.13 GB of scores, recomputed in the
backward, and keys and values are never repeated over their groups.
"""
from __future__ import annotations

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp

from benchmark.reference.deepseek_v3 import (  # nothing of a model in these
    _mm,
    _rms_norm,
    flatten,
    flatten_samples,
    leaf_diff_norms,
    leaf_norms,
    sample_elements,
    to_reference,
)
from benchmark.reference.gpt2 import (
    PRECISIONS,
    _accumulate,
    _name,
    adamw_update,
)
from benchmark.reference.keye_vl2 import _rotary, experts   # the same layer

ROWS = 64      # query rows of one block of the attention over all pairs
CHUNK = 2048   # rows the held experts, and the head, take at a time


@dataclasses.dataclass(frozen=True)
class Settings:
    """What the weights' shapes do not say."""
    top_k: int
    first_held: int
    theta: float
    eps: float
    block_length: int
    noise_eps: float
    noise_seed: int
    mask_id: int
    train_router: bool = True
    embed_scale: float = 1.0
    wrong: str = ""         # "causal_mask": another model (the docstring)

    @classmethod
    def from_config(cls, cfg: dict) -> "Settings":
        bd = cfg["block_diffusion"]
        return cls(top_k=int(cfg["num_experts_per_tok"]),
                   first_held=int(cfg["deployment"]["experts_held"][0]),
                   theta=float(cfg["rope_theta"]),
                   eps=float(cfg["rms_norm_eps"]),
                   block_length=int(bd["block_length"]),
                   noise_eps=float(bd["noise_eps"]),
                   noise_seed=int(bd["noise_seed"]),
                   mask_id=int(bd["mask_token_id"]),
                   train_router=bool(cfg.get("train_router", True)),
                   embed_scale=float(cfg.get("embed_scale", 1.0)))


def noised(x, s: Settings):
    """``(x~ [L], m / t [L])`` of one sequence ``x [L]`` by the stated
    rule."""
    length = x.shape[0]
    place = jnp.arange(length, dtype=jnp.uint32)
    d = jnp.sum((x.astype(jnp.uint32) + jnp.uint32(1))
                * (jnp.uint32(2) * place + jnp.uint32(1)), dtype=jnp.uint32)
    for_levels, for_masks = jax.random.split(
        jax.random.fold_in(jax.random.key(s.noise_seed), d))
    u = jax.random.uniform(for_levels, (length // s.block_length,),
                           jnp.float32)
    t = (s.noise_eps + (1.0 - s.noise_eps) * u)[
        jnp.arange(length) // s.block_length]
    m = jax.random.uniform(for_masks, (length,), jnp.float32) < t
    return jnp.where(m, s.mask_id, x).astype(x.dtype), \
        jnp.where(m, 1.0 / t, 0.0)


def sees(r, c, length: int, s: Settings):
    """The mask on index grids: query rows ``r [.., 1]`` against key rows
    ``c [1, ..]`` of the ``2 L`` rows ``[x ; x~]``."""
    if s.wrong == "causal_mask":
        return c <= r
    if s.wrong:
        raise ValueError(f"no other model named {s.wrong!r}")
    b = s.block_length
    r_clean, c_clean = r < length, c < length
    clean_clean = r_clean & c_clean & (c // b <= r // b)
    noised_clean = ~r_clean & c_clean & (c // b < (r - length) // b)
    noised_noised = ~r_clean & ~c_clean \
        & ((c - length) // b == (r - length) // b)
    return clean_clean | noised_clean | noised_noised


def _attention(x, a, s: Settings, products):
    """``x [2 L, D]``, one sequence and its noised copy: all pairs' scores,
    ``ROWS`` queries at a time, the mask by the rules; each block
    recomputed in the backward.  Query head ``g * (H / G) + j`` reads key
    head ``g``: the heads are laid out ``[G, H / G]`` and no key is
    repeated."""
    rows = x.shape[0]
    length = rows // 2
    heads, groups = a["wq"].shape[1], a["wk"].shape[1]
    every = jnp.arange(rows)
    k = _rotary(_rms_norm(_mm("td,dgk->tgk", x, a["wk"], products, (-1,),
                              (0,)), a["k_norm"]["scale"], s.eps), s.theta,
                every % length)
    v = _mm("td,dgk->tgk", x, a["wv"], products, (-1,), (0,))
    block_rows = ROWS if rows % ROWS == 0 else rows

    @jax.checkpoint
    def block(start):
        at = start + jnp.arange(block_rows)
        xq = jax.lax.dynamic_slice_in_dim(x, start, block_rows, axis=0)
        q = _rotary(_rms_norm(_mm("td,dhk->thk", xq, a["wq"], products,
                                  (-1,), (0,)), a["q_norm"]["scale"], s.eps),
                    s.theta, at % length)
        q = q.reshape(block_rows, groups, heads // groups, q.shape[-1])
        logits = _mm("qgjd,kgd->gjqk", q, k, products) / (q.shape[-1] ** 0.5)
        keep = sees(at[:, None], every[None, :], length, s)
        logits = jnp.where(keep[None, None], logits.astype(jnp.float32),
                           -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        return _mm("gjqk,kgd->qgjd", probs, v, products).reshape(
            block_rows, heads, v.shape[-1])

    o = jax.lax.map(block, jnp.arange(0, rows, block_rows)).reshape(
        rows, heads, v.shape[-1])
    return _mm("thv,hvd->td", o, a["wo"], products, (-2, -1), (0, 1))


def _experts(x, m, s: Settings, products):
    """``keye_vl2.experts`` over ``x [2 L, D]``, ``CHUNK`` rows at a time,
    each chunk recomputed in the backward (what a scan over the experts
    keeps is then a chunk's and not the sequence's)."""
    rows = x.shape[0]
    size = CHUNK if rows % CHUNK == 0 else rows
    return jax.lax.map(
        jax.checkpoint(lambda part: experts(part, m, products, s=s)),
        x.reshape(rows // size, size, x.shape[-1])).reshape(x.shape)


def _layer(x, lp, s: Settings, products):
    x = x + _attention(_rms_norm(x, lp["ln_attn"]["scale"], s.eps),
                       lp["attn"], s, products)
    return x + _experts(_rms_norm(x, lp["ln_mlp"]["scale"], s.eps),
                        lp["moe"], s, products)


def features(ref, ids, compute="float32", *, s: Settings):
    """The last layer's output ``[B, R, D]`` for the rows ``ids [B, R]``
    (``R = 2 L``: a sequence and its noised copy), before the final norm."""
    dtype, products = PRECISIONS[_name(compute)]
    ref = jax.tree_util.tree_map(lambda a: a.astype(dtype), ref)
    x = ref["embed"][ids]
    if s.embed_scale != 1.0:
        x = x * jnp.asarray(s.embed_scale, x.dtype)
    for i in range(sum(k.startswith("layers_") for k in ref)):
        lp = ref[f"layers_{i}"]
        x = jax.lax.map(jax.checkpoint(
            lambda row, lp=lp: _layer(row, lp, s, products)), x)
    return x


def sum_loss_fn(ref, tokens, compute="float32", scale=1.0, *, s: Settings):
    """SUM over sequences and tokens of ``(m_i / t) CE(z_i, x_i)`` times
    ``scale``; the layers one sequence at a time and the head ``CHUNK``
    rows at a time, each recomputed in the backward."""
    dtype, products = PRECISIONS[_name(compute)]
    length = tokens.shape[1]
    copies, weights = jax.vmap(lambda x: noised(x, s))(tokens)
    y = features(ref, jnp.concatenate([tokens, copies], axis=1), compute,
                 s=s)[:, length:]
    ref = jax.tree_util.tree_map(lambda a: a.astype(dtype), ref)
    y = _rms_norm(y, ref["ln_final"]["scale"], s.eps)

    @jax.checkpoint
    def part(args):
        y_part, x_part, w_part = args
        logits = _mm("td,vd->tv", y_part, ref["head"], products, (-1,),
                     (1,)).astype(jnp.float32)
        picked = jnp.take_along_axis(logits, x_part[:, None], -1)[:, 0]
        return jnp.sum(w_part * (jax.nn.logsumexp(logits, axis=-1) - picked))

    size = CHUNK if tokens.size % CHUNK == 0 else length
    parts = (y.reshape(-1, size, y.shape[-1]), tokens.reshape(-1, size),
             weights.reshape(-1, size))
    return jnp.sum(jax.lax.map(part, parts)) * scale


@functools.partial(jax.jit, static_argnames=("compute", "scale", "s"))
def _block_grad(ref, tokens, compute, scale, s):
    return jax.value_and_grad(
        lambda p: sum_loss_fn(p, tokens, compute, scale, s=s))(ref)


def lowered_block_grad(ref, tokens, compute="float32", *,
                       s: Settings) -> str:
    return _block_grad.lower(ref, tokens, _name(compute), 1.0, s).as_text()


def loss_and_grads(ref, tokens, *, row_block=1, compute="float32",
                   s: Settings):
    """Mean loss and its float32 gradients over ``tokens`` [B, L],
    ``row_block`` sequences a backward pass."""
    rows, length = tokens.shape
    total, acc = 0.0, None
    for r in range(0, rows, row_block):
        loss, grads = _block_grad(ref, tokens[r:r + row_block],
                                  _name(compute), 1.0 / (rows * length), s)
        total = total + loss
        acc = (jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
               if acc is None else _accumulate(acc, grads))
    return total, acc


def train_steps(ref, batches, *, row_block=1, compute="float32",
                sample_seed=0, s: Settings):
    """``deepseek_v3.train_steps`` for this model: the loss of every step,
    the per-leaf norm of the first gradient, the per-leaf norm of the
    parameters' change after the last step, and the sampled elements of
    the first gradient."""
    p0 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), ref)
    p = p0
    mu = jax.tree_util.tree_map(jnp.zeros_like, p0)
    nu = jax.tree_util.tree_map(jnp.zeros_like, p0)
    count = jnp.zeros((), jnp.int32)
    losses, first, sample = [], None, None
    for tokens in batches:
        loss, grads = loss_and_grads(p, tokens, row_block=row_block,
                                     compute=compute, s=s)
        if first is None:
            first = flatten(leaf_norms(grads))
            sample = flatten_samples(sample_elements(grads, sample_seed))
        p, mu, nu, count = adamw_update(p, grads, mu, nu, count)
        del grads
        losses.append(float(loss))
    return losses, first, flatten(leaf_diff_norms(p, p0)), sample


def bound(config_path: str) -> dict:
    """The functions the benchmark calls, bound to the configuration at
    ``config_path`` (``deepseek_v3.bound`` says why)."""
    with open(config_path) as f:
        s = Settings.from_config(json.load(f))
    out = {name: functools.partial(globals()[name], s=s)
           for name in ("train_steps", "lowered_block_grad", "features",
                        "sum_loss_fn")}
    out.update(SETTINGS=s, to_reference=to_reference, flatten=flatten,
               flatten_samples=flatten_samples, leaf_norms=leaf_norms,
               leaf_diff_norms=leaf_diff_norms,
               sample_elements=sample_elements)
    return out
