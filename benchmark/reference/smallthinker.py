"""The plain language model of SmallThinker (``model_name:
smallthinker_21b_instruct``): forward pass, loss, gradients and AdamW in
``jax.numpy``, for the whole block and for ONE CHIP'S SHARE of it under
expert parallelism.

Layers of two kinds by index, as the configuration's two layouts say:
``sliding_window_layout[l]`` and ``rope_layout[l]`` both 0 is a GLOBAL
layer (every earlier key, no positions at all), both 1 a WINDOW layer
(rotary at ``rope_theta`` over all of a head's columns, half against
half; the query's own position and the ``sliding_window_size - 1``
before it).  ``x_0`` is ``embed_scale`` (the configuration's key of that
name; 1 where it has none) times the table's rows; ``x_l [T, D]`` enters
layer ``l``:

    r    = x_l W_r                      float32, exact: the layer's INPUT
    h    = RMSNorm(x_l)
    q_i  = h W_q, k_g = h W_k, v_g = h W_v      no bias, no per-head norm
    q, k = rotary(q), rotary(k)         where rope_layout[l]
    a_i[t] = softmax over s <= t (and s > t - window where
             sliding_window_layout[l]) of (q_i[t] . k_g[s] / sqrt(Dh))
             applied to v_g;  query head i reads key/value head i // (H / G)
    u    = x_l + concat_i(a_i) W_o
    m    = RMSNorm(u)
    p    = softmax(r) over all E;  S = top-k of p;  g_e = p_e / sum_S p
           (as the top-k of r and the softmax over the picked logits)
    x_{l+1} = u + sum_{e in S} g_e (relu(m W_gate,e) * (m W_up,e)) W_down,e
    end  RMSNorm, logits = x W_head, mean next-token cross entropy

The router reads the layer's input BEFORE the attention norm; its experts
read the normed stream after attention.  The gradients are the
cross-entropy's with ``S`` held constant.  Under the share
(``deployment.experts_held``) the experts' sum runs over the held ones,
normalised over all of ``S`` (``reference/deepseek_v3.py`` says the same
of its layer).  ``relu``'s derivative at 0 is 0 (``jax.nn.relu``).

It imports nothing of the program and none of its mechanisms: the scores
of ALL pairs are formed, ``ROWS`` queries at a time against every key,
and the window is a mask built from the positions; every held expert is
applied to every token and weighted by a mask.  The router's product is
exact float32 in every precision but bfloat16 storage.  Weights by the
program's names:

    embed, head [V, D], ln_final/scale, layers_<i>/{ln_attn/scale,
    attn/{wq [D,H,Dh], wk, wv [D,G,Dh], wo [H,Dh,D]}, ln_mlp/scale,
    moe/{router [D,E], experts/{w_gate,w_up [held,D,F], w_down [held,F,D]}}}

Memory at the cell's size (371 M parameters, one sequence of 16,384):
five copies of the weights are 7.4 GB; a block of 256 queries against
16,384 keys over 28 heads is 0.47 GB of scores, recomputed in the
backward.
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
from benchmark.reference.keye_vl2 import _rotary     # rotate_half

ROWS = 256    # query rows of one block of the attention over all pairs


@dataclasses.dataclass(frozen=True)
class Settings:
    """What the weights' shapes do not say."""
    top_k: int
    first_held: int
    window: int
    window_layout: tuple
    rope_layout: tuple
    theta: float
    eps: float
    train_router: bool = True
    embed_scale: float = 1.0

    @classmethod
    def from_config(cls, cfg: dict) -> "Settings":
        return cls(top_k=int(cfg["moe_num_active_primary_experts"]),
                   first_held=int(cfg["deployment"]["experts_held"][0]),
                   window=int(cfg["sliding_window_size"]),
                   window_layout=tuple(cfg["sliding_window_layout"]),
                   rope_layout=tuple(cfg["rope_layout"]),
                   theta=float(cfg["rope_theta"]),
                   eps=float(cfg["rms_norm_eps"]),
                   train_router=bool(cfg.get("train_router", True)),
                   embed_scale=float(cfg.get("embed_scale", 1.0)))


def _attention(x, a, windowed, turned, s: Settings, products):
    """``x [T, D]``, one sequence: all pairs' scores, ``ROWS`` queries at a
    time, the causal triangle (and the window) as a mask from the
    positions; each block recomputed in the backward."""
    t = x.shape[0]
    heads, groups = a["wq"].shape[1], a["wk"].shape[1]
    every = jnp.arange(t)
    k = _mm("td,dgk->tgk", x, a["wk"], products, (-1,), (0,))
    if turned:
        k = _rotary(k, s.theta, every)
    v = _mm("td,dgk->tgk", x, a["wv"], products, (-1,), (0,))
    k, v = (jnp.repeat(y, heads // groups, axis=1) for y in (k, v))
    rows = ROWS if t % ROWS == 0 else t

    @jax.checkpoint
    def block(start):
        pos = start + jnp.arange(rows)
        xq = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
        q = _mm("td,dhk->thk", xq, a["wq"], products, (-1,), (0,))
        if turned:
            q = _rotary(q, s.theta, pos)
        logits = _mm("qhd,khd->hqk", q, k, products) / (q.shape[-1] ** 0.5)
        keep = every[None, :] <= pos[:, None]
        if windowed:
            keep &= every[None, :] > pos[:, None] - s.window
        logits = jnp.where(keep[None], logits.astype(jnp.float32), -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        return _mm("hqk,khd->qhd", probs, v, products)

    o = jax.lax.map(block, jnp.arange(0, t, rows)).reshape(
        t, heads, v.shape[-1])
    return _mm("thv,hvd->td", o, a["wo"], products, (-2, -1), (0, 1))


def expert_weights(entered, m, s: Settings):
    """``g [.., E]``: ``p_e / sum_{j in S} p_j`` on the ``top_k`` experts of
    ``S`` by ``p = softmax(entered W_r)`` and 0 elsewhere; ``entered``:
    what the router reads, the layer's input.  ``S`` is taken as the
    ``top_k`` largest LOGITS (``top_k`` rounds of argmax; the lowest index
    wins a tie), which are the largest of ``p`` wherever float32 tells them
    apart, and ``g`` as the softmax over the picked logits, which is ``p_e
    / sum_S p_j``: where the logits stand more than 87 apart ``p``
    underflows to 0 and a selection by ``p`` would be a tie that float32
    made, not the model."""
    router = m["router"] if s.train_router \
        else jax.lax.stop_gradient(m["router"])
    logits = jnp.einsum("...d,de->...e", entered, router,
                        preferred_element_type=jnp.float32)
    left, chosen = logits, jnp.zeros(logits.shape, bool)
    for _ in range(s.top_k):
        best = jax.nn.one_hot(jnp.argmax(left, -1), logits.shape[-1],
                              dtype=bool)
        chosen |= best
        left = jnp.where(best, -jnp.inf, left)
    return jax.nn.softmax(jnp.where(chosen, logits, -jnp.inf), axis=-1)


def _reglu(x, w, products):
    gate = _mm("...d,df->...f", x, w["w_gate"], products, (-1,), (0,))
    up = _mm("...d,df->...f", x, w["w_up"], products, (-1,), (0,))
    return _mm("...f,fd->...d", jax.nn.relu(gate) * up, w["w_down"],
               products, (-1,), (0,))


def experts(x, entered, m, products=None, *, s: Settings):
    """The held experts of ``S`` (chosen from ``entered``, the layer's
    input), each applied to every token of ``x`` and weighted by ``g`` (0
    where it was not chosen)."""
    held = m["experts"]["w_gate"].shape[0]
    g = expert_weights(entered, m, s)[..., s.first_held:s.first_held + held]

    @jax.checkpoint
    def one(y, expert):
        w, g_e = expert
        return y + g_e[..., None].astype(x.dtype) \
            * _reglu(x, w, products), None

    return jax.lax.scan(one, jnp.zeros_like(x),
                        (m["experts"], jnp.moveaxis(g, -1, 0)))[0]


def _layer(x, lp, windowed, turned, s: Settings, products):
    u = x + _attention(_rms_norm(x, lp["ln_attn"]["scale"], s.eps),
                       lp["attn"], windowed, turned, s, products)
    return u + experts(_rms_norm(u, lp["ln_mlp"]["scale"], s.eps), x,
                       lp["moe"], products, s=s)


def sum_loss_fn(ref, tokens, compute="float32", scale=1.0, *, s: Settings):
    """SUM over rows and positions of the next-token cross entropy times
    ``scale``; layers and head one sequence at a time, each recomputed in
    the backward."""
    dtype, products = PRECISIONS[_name(compute)]
    ref = jax.tree_util.tree_map(lambda a: a.astype(dtype), ref)
    x = ref["embed"][tokens] * jnp.asarray(s.embed_scale, dtype)
    for i in range(sum(k.startswith("layers_") for k in ref)):
        lp = ref[f"layers_{i}"]
        kind = bool(s.window_layout[i]), bool(s.rope_layout[i])
        x = jax.lax.map(jax.checkpoint(
            lambda row, lp=lp, kind=kind: _layer(row, lp, *kind, s,
                                                 products)), x)
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
