"""The plain language model of ``model_type: KeyeVL2``: forward pass, loss,
gradients and AdamW in ``jax.numpy``, for the whole block and for ONE
CHIP'S SHARE of it under expert parallelism.

A Qwen3-MoE block whose attention is DeepSeek sparse attention
(DeepSeek-AI 2025, the DeepSeek-V3.2-Exp report: the index score and the
top-k selection), as the configuration's keys spell it out:

    block    h = x + Attn(RMSNorm(x));  y = h + Experts(RMSNorm(h))
    heads    q_i = RMSNorm(x W_q)_i, k_g = RMSNorm(x W_k)_g (over the head's
             width), v_g = x W_v; rotary, half against half, on q and k;
             query head i reads key/value head i // (H / G)
    indexer  qI_j = x W_qI, kI = LayerNorm(x W_kI), w = x W_w, rotary on
             qI and kI;  I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])
             S_t = the topk largest of I[t, 0..t]  (all where t < topk)
    Attn     o_i[t] = softmax over s in S_t of (q_i[t] . k_g[s] / sqrt(Dh))
             applied to v_g;  Attn = concat_i(o_i) W_o
    Experts  p = softmax(x W_r) over all E;  S = top-k of p
             g_e = p_e / sum_{j in S} p_j;  Experts = sum_{e in S} g_e E_e(x)
    end      RMSNorm, logits = y W_head, mean next-token cross entropy

The gradients are the cross-entropy's with ``S_t`` and ``S`` held
constant; nothing reaches the indexer's leaves, whose gradient is zero.
Under the share (``deployment.experts_held``) the experts' sum runs over
the held ones, normalised over all of ``S`` (``reference/deepseek_v3.py``
says the same of its layer).

It imports nothing of the program and none of its mechanisms: the scores
of ALL pairs are formed, ``ROWS`` queries at a time against every key,
and the selection is a mask made from ``jax.lax.top_k`` (its k-th value,
and the last position it took at that value); every held expert is
applied to every token and weighted by a mask.  The index scores and the
router's product are exact float32 in every precision but bfloat16
storage.  Weights by the program's names:

    embed, head [V, D], ln_final/scale, layers_<i>/{ln_attn/scale,
    attn/{wq [D,H,Dh], wk, wv [D,G,Dh], q_norm/scale, k_norm/scale [Dh],
    wo [H,Dh,D]}, indexer/{wq [D,J,Di], wk [D,Di], k_norm/{scale,bias}
    [Di], weights [D,J]}, ln_mlp/scale, moe/{router [D,E],
    experts/{w_gate,w_up [held,D,F], w_down [held,F,D]}}}

Memory at the cell's size (465 M parameters, one sequence of 16,384):
five copies of the weights are 9.3 GB; a block of 256 queries against
16,384 keys over 32 heads is 0.5 GB of scores, recomputed in the backward.
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
    _swiglu,
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

ROWS = 256    # query rows of one block of the attention over all pairs


@dataclasses.dataclass(frozen=True)
class Settings:
    """What the weights' shapes do not say."""
    top_k: int
    first_held: int
    topk: int
    theta: float
    eps: float
    train_router: bool = True

    @classmethod
    def from_config(cls, cfg: dict) -> "Settings":
        return cls(top_k=int(cfg["num_experts_per_tok"]),
                   first_held=int(cfg["deployment"]["experts_held"][0]),
                   topk=int(cfg["sa_config"]["topk"]),
                   theta=float(cfg["rope_theta"]),
                   eps=float(cfg["rms_norm_eps"]),
                   train_router=bool(cfg.get("train_router", True)))


def _rotary(x, theta, pos):
    """``x [T, ..., R]`` at positions ``pos [T]``: the pair ``(x[i], x[i +
    R/2])`` turned by ``pos * theta^(-2i/R)`` (rotate_half)."""
    r = x.shape[-1]
    freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = (pos.astype(jnp.float32)[:, None] * freq).reshape(
        pos.shape + (1,) * (x.ndim - 2) + (r // 2,))
    a, b = x[..., :r // 2].astype(jnp.float32), \
        x[..., r // 2:].astype(jnp.float32)
    return jnp.concatenate(
        [a * jnp.cos(angle) - b * jnp.sin(angle),
         a * jnp.sin(angle) + b * jnp.cos(angle)], -1).astype(x.dtype)


def _layer_norm(x, p, eps):
    x32 = x.astype(jnp.float32)
    centred = x32 - x32.mean(-1, keepdims=True)
    return (centred * jax.lax.rsqrt((centred ** 2).mean(-1, keepdims=True)
                                    + eps) * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


def _exact(eq, a, b):
    """A product kept out of the precisions: float32 sums of the operands
    as stored."""
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)


def selected(xq, ix, ki, pos, s: Settings):
    """``[rows, T]`` bool: the keys that the rows ``xq [rows, D]`` (the
    layer's normed input at positions ``pos``) attend to; ``ki [T, Di]``
    the indexer's keys."""
    t = ki.shape[0]
    qi = _rotary(_exact("td,djk->tjk", xq, ix["wq"]), s.theta, pos)
    w = _exact("td,dj->tj", xq, ix["weights"])
    score = jnp.einsum("tj,tjs->ts", w.astype(jnp.float32), jax.nn.relu(
        _exact("tjk,sk->tjs", qi, ki)), precision=jax.lax.Precision.HIGHEST)
    col = jnp.arange(t)[None, :]
    seen = col <= pos[:, None]
    score = jnp.where(seen, score, -jnp.inf)
    values, places = jax.lax.top_k(score, min(s.topk, t))
    kth = values[:, -1:]
    last = jnp.max(jnp.where(values == kth, places, -1), axis=-1,
                   keepdims=True)
    return ((score > kth) | ((score == kth) & (col <= last))) & seen


def _attention(x, a, ix, s: Settings, products):
    """``x [T, D]``, one sequence: all pairs' scores, ``ROWS`` queries at a
    time, the selection as a mask; each block recomputed in the
    backward."""
    t = x.shape[0]
    heads, groups = a["wq"].shape[1], a["wk"].shape[1]
    every = jnp.arange(t)
    k = _rotary(_rms_norm(_mm("td,dgk->tgk", x, a["wk"], products, (-1,),
                              (0,)), a["k_norm"]["scale"], s.eps), s.theta,
                every)
    v = _mm("td,dgk->tgk", x, a["wv"], products, (-1,), (0,))
    k, v = (jnp.repeat(y, heads // groups, axis=1) for y in (k, v))
    xi, ix = jax.lax.stop_gradient((x, ix))
    ki = _rotary(_layer_norm(_exact("td,dk->tk", xi, ix["wk"]), ix["k_norm"],
                             s.eps), s.theta, every)
    rows = ROWS if t % ROWS == 0 else t

    @jax.checkpoint
    def block(start):
        pos = start + jnp.arange(rows)
        xq = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
        q = _rotary(_rms_norm(_mm("td,dhk->thk", xq, a["wq"], products,
                                  (-1,), (0,)), a["q_norm"]["scale"], s.eps),
                    s.theta, pos)
        logits = _mm("qhd,khd->hqk", q, k, products) / (q.shape[-1] ** 0.5)
        keep = selected(jax.lax.stop_gradient(xq), ix, ki, pos, s)
        logits = jnp.where(keep[None], logits.astype(jnp.float32), -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        return _mm("hqk,khd->qhd", probs, v, products)

    o = jax.lax.map(block, jnp.arange(0, t, rows)).reshape(
        t, heads, v.shape[-1])
    return _mm("thv,hvd->td", o, a["wo"], products, (-2, -1), (0, 1))


def expert_weights(x, m, s: Settings):
    """``g [.., E]``: ``p_e / sum_{j in S} p_j`` on the ``top_k`` experts of
    ``S`` by ``p = softmax(x W_r)`` and 0 elsewhere (``top_k`` rounds of
    argmax; the lowest index wins a tie)."""
    router = m["router"] if s.train_router \
        else jax.lax.stop_gradient(m["router"])
    p = jax.nn.softmax(jnp.einsum(
        "...d,de->...e", x, router, preferred_element_type=jnp.float32),
        axis=-1)
    left, chosen = p, jnp.zeros(p.shape, bool)
    for _ in range(s.top_k):
        best = jax.nn.one_hot(jnp.argmax(left, -1), p.shape[-1], dtype=bool)
        chosen |= best
        left = jnp.where(best, -jnp.inf, left)
    picked = jnp.where(chosen, p, 0.0)
    return picked / picked.sum(-1, keepdims=True)


def experts(x, m, products=None, *, s: Settings):
    """The held experts of ``S``, each applied to every token and weighted
    by ``g`` (0 where it was not chosen)."""
    held = m["experts"]["w_gate"].shape[0]
    g = expert_weights(x, m, s)[..., s.first_held:s.first_held + held]

    @jax.checkpoint
    def one(y, expert):
        w, g_e = expert
        return y + g_e[..., None].astype(x.dtype) \
            * _swiglu(x, w, products), None

    return jax.lax.scan(one, jnp.zeros_like(x),
                        (m["experts"], jnp.moveaxis(g, -1, 0)))[0]


def _layer(x, lp, s: Settings, products):
    x = x + _attention(_rms_norm(x, lp["ln_attn"]["scale"], s.eps),
                       lp["attn"], lp["indexer"], s, products)
    return x + experts(_rms_norm(x, lp["ln_mlp"]["scale"], s.eps),
                       lp["moe"], products, s=s)


def sum_loss_fn(ref, tokens, compute="float32", scale=1.0, *, s: Settings):
    """SUM over rows and positions of the next-token cross entropy times
    ``scale``; layers and head one sequence at a time, each recomputed in
    the backward."""
    dtype, products = PRECISIONS[_name(compute)]
    ref = jax.tree_util.tree_map(lambda a: a.astype(dtype), ref)
    x = ref["embed"][tokens]
    for i in range(sum(k.startswith("layers_") for k in ref)):
        lp = ref[f"layers_{i}"]
        x = jax.lax.map(jax.checkpoint(
            lambda row, lp=lp: _layer(row, lp, s, products)), x)
    x = _rms_norm(x, ref["ln_final"]["scale"], s.eps)

    @jax.checkpoint
    def row(args):
        x_row, t_row = args
        logits = _mm("td,vd->tv", x_row[:-1], ref["head"], products, (-1,),
                     (1,)).astype(jnp.float32)
        picked = jnp.take_along_axis(logits, t_row[1:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    return jnp.sum(jax.lax.map(row, (x, tokens))) * scale


@functools.partial(jax.jit, static_argnames=("compute", "scale", "s"))
def _block_grad(ref, tokens, compute, scale, s):
    return jax.value_and_grad(
        lambda p: sum_loss_fn(p, tokens, compute, scale, s=s))(ref)


def lowered_block_grad(ref, tokens, compute="float32", *,
                       s: Settings) -> str:
    return _block_grad.lower(ref, tokens, _name(compute), 1.0, s).as_text()


def loss_and_grads(ref, tokens, *, row_block=1, compute="float32",
                   s: Settings):
    """Mean loss and its float32 gradients over ``tokens`` [B, T],
    ``row_block`` rows a backward pass."""
    rows, t = tokens.shape
    total, acc = 0.0, None
    for r in range(0, rows, row_block):
        loss, grads = _block_grad(ref, tokens[r:r + row_block],
                                  _name(compute), 1.0 / (rows * (t - 1)), s)
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
           for name in ("train_steps", "lowered_block_grad")}
    out.update(SETTINGS=s, to_reference=to_reference, flatten=flatten,
               flatten_samples=flatten_samples, leaf_norms=leaf_norms,
               leaf_diff_norms=leaf_diff_norms,
               sample_elements=sample_elements)
    return out
