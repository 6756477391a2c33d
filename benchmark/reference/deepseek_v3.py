"""The plain DeepSeek-V3 block: forward pass, loss, gradients and AdamW in
``jax.numpy``, for a configuration of ``model_type: deepseek_v3`` and for
ONE CHIP'S SHARE of it under expert parallelism.

Follows DeepSeek-V3 (arxiv 2412.19437 §2.1) as its ``config.json`` keys
spell it out:

    block   h = x + MLA(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    MLA     q_i = x W_q = [q_nope, q_rope]      (no query low-rank)
            [c, k_rope] = x W_kva;  c' = RMSNorm(c)
            [k_nope_i, v_i] = c' W_kvb
            rotary on q_rope_i and on the ONE k_rope all heads share
            k_i = [k_nope_i, k_rope]
            o_i = softmax_causal(q_i k_i^T / sqrt(nope + rope)) v_i
            MLA = concat_i(o_i) W_o
    FFN     leading dense layers: W_down(silu(W_gate x) * W_up x)
            expert layers: s = sigmoid(x W_r);  S = top-k of (s + b)
            g_e = routed_scaling_factor * s_e / sum_{j in S} s_j
            FFN = Shared(x) + sum_{e in S} g_e E_e(x)
    end     RMSNorm, logits = y W_head, mean next-token cross entropy

**Under the share** (``experts_held = [first, count]`` in the configuration
file): the sum runs over the experts of ``S`` that are held, the weights
are still normalised over all of ``S``, and what the absent experts would
add is left out.  With ``first = 0`` and all experts held this is the
whole layer.

Departures, as the configuration file lists them: rotary turns the
interleaved pairs in place (the checkpoint's code de-interleaves first;
queries and keys get the same reordering, so every q.k is the same); the
selection bias ``b`` takes no gradient and is not updated from the load;
and where the file says ``"train_router": false`` the routers' weights
take no gradient either (the scores still pass theirs on to ``x``).

It imports nothing of the program, and none of its mechanisms: attention
is the dense softmax over blocks of query rows (so that the score matrix
fits), the selection is ``k`` rounds of argmax, and every held expert is
applied to EVERY token and weighted by a mask: no sort, no gather, no
grouped product, no kernel.  ``jax.checkpoint`` per layer and per block
is recomputation, not a kernel.  The weights are the tree the benchmark
made from the seed, read by the program's names (the one thing the two
share):

    embed [V, D], head [V, D], ln_final/scale [D],
    layers_<i>/{ln_attn/scale, attn/{wq [D,H,nope+rope], wkv_a [D,c+rope],
    kv_norm/scale [c], wkv_b [c,H,nope+Dv], wo [H,Dv,D]}, ln_mlp/scale,
    mlp/{w_gate,w_up [D,F], w_down [F,D]}  or  moe/{router [D,E],
    router_bias [E], shared/{...}, experts/{w_gate,w_up [held,D,Fe],
    w_down [held,Fe,D]}}}

What shapes do not give (experts a token, the scaling factor, the first
held expert, the nope/rope split, theta, epsilon) comes from the
configuration file: :func:`bound` reads it and returns the functions
``entries/train.py`` and ``control.py`` call, bound to it
(``reference/kanana_2_30b_a3b_ep8_share.py`` is those few lines).  The
precisions (``compute``) are ``reference/gpt2.py``'s, helpers and all; the
router's product is exact float32 in every one of them but bfloat16
storage (the selection must not turn on rounding).

Memory, at the cell's size (576 M parameters): the caller's weights, the
stepped weights, AdamW's two moments and one gradient are 11.5 GB, so the
WHOLE batch goes through one backward pass (``row_block`` = the batch: no
second gradient to add up), and inside it every layer and the head take
one sequence at a time and attention 256 query rows at a time.
"""
from __future__ import annotations

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp

from benchmark.reference import gpt2
from benchmark.reference.gpt2 import (  # the precisions and AdamW
    PRECISIONS,
    _accumulate,
    _name,
    _sample_idx,
    adamw_update,
)

ATTENTION_ROWS = 256    # query rows of one block of the dense attention


@dataclasses.dataclass(frozen=True)
class Settings:
    """What the weights' shapes do not say."""
    top_k: int
    routed_scale: float
    first_held: int
    qk_nope: int
    theta: float
    eps: float
    train_router: bool = True

    @classmethod
    def from_config(cls, cfg: dict) -> "Settings":
        return cls(top_k=int(cfg["num_experts_per_tok"]),
                   routed_scale=float(cfg["routed_scaling_factor"]),
                   first_held=int(cfg["deployment"]["experts_held"][0]),
                   qk_nope=int(cfg["qk_nope_head_dim"]),
                   theta=float(cfg["rope_theta"]),
                   eps=float(cfg["rms_norm_eps"]),
                   train_router=bool(cfg.get("train_router", True)))


def _mm(eq, a, w, products, a_axes=None, w_axes=None):
    """``gpt2._mm``.  Where both operands are rounded to bfloat16 first,
    the product asks for ONE pass: numbers on the bfloat16 grid multiply
    exactly in one, the sums are float32 either way, and the five further
    passes that the callers' ``highest`` would spend on their zero low
    parts are a reference six times as slow (150 s for 50 at this size)."""
    if products == "bfloat16":
        return gpt2._result(jnp.einsum(
            eq, gpt2._operand(a), gpt2._operand(w),
            precision=jax.lax.Precision.DEFAULT))
    return gpt2._mm(eq, a, w, products, a_axes, w_axes)


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt((x32 ** 2).mean(-1, keepdims=True) + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def _rotary(x, theta):
    """Pairs ``(x[2i], x[2i+1])`` of position ``t`` turned by
    ``t * theta^(-2i/R)``; ``x`` [B, T, H, R]."""
    t, r = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None]
             * freq)[None, :, None, :]
    even, odd = x[..., 0::2].astype(jnp.float32), \
        x[..., 1::2].astype(jnp.float32)
    turned = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                        even * jnp.sin(angle) + odd * jnp.cos(angle)], -1)
    return turned.reshape(x.shape).astype(x.dtype)


def _attention(q, k, v, products):
    """Causal softmax(q k^T / sqrt(Dk)) v; q, k [B, T, H, Dk], v
    [B, T, H, Dv]; ``ATTENTION_ROWS`` query rows at a time against all
    keys, each block recomputed in the backward pass."""
    t = q.shape[1]
    rows = min(ATTENTION_ROWS, t)
    if t % rows:
        rows = t
    col = jnp.arange(t)

    @jax.checkpoint
    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=1)
        logits = _mm("bqhd,bkhd->bhqk", qb, k, products) \
            / (q.shape[-1] ** 0.5)
        seen = col[None, :] <= (start + jnp.arange(rows))[:, None]
        logits = jnp.where(seen, logits.astype(jnp.float32), -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return _mm("bhqk,bkhd->bqhd", probs, v, products)

    out = jax.lax.map(block, jnp.arange(0, t, rows))    # [nb,B,rows,H,Dv]
    return jnp.moveaxis(out, 0, 1).reshape(
        q.shape[0], t, q.shape[2], v.shape[-1])


def _latent_attention(x, a, s: Settings, products):
    latent = a["kv_norm"]["scale"].shape[0]
    heads = a["wq"].shape[1]
    q = _mm("btd,dhk->bthk", x, a["wq"], products, (-1,), (0,))
    kv_a = _mm("btd,dk->btk", x, a["wkv_a"], products, (-1,), (0,))
    c = _rms_norm(kv_a[..., :latent], a["kv_norm"]["scale"], s.eps)
    k_rope = _rotary(kv_a[:, :, None, latent:], s.theta)      # [B,T,1,R]
    kv = _mm("btc,chk->bthk", c, a["wkv_b"], products, (-1,), (0,))
    q = jnp.concatenate([q[..., :s.qk_nope],
                         _rotary(q[..., s.qk_nope:], s.theta)], -1)
    k = jnp.concatenate([kv[..., :s.qk_nope],
                         jnp.repeat(k_rope, heads, axis=2)], -1)
    o = _attention(q, k, kv[..., s.qk_nope:], products)
    return _mm("bthv,hvd->btd", o, a["wo"], products, (-2, -1), (0, 1))


def _swiglu(x, w, products):
    gate = _mm("...d,df->...f", x, w["w_gate"], products, (-1,), (0,))
    up = _mm("...d,df->...f", x, w["w_up"], products, (-1,), (0,))
    return _mm("...f,fd->...d", jax.nn.silu(gate) * up, w["w_down"],
               products, (-1,), (0,))


def expert_weights(x, m, s: Settings):
    """``g`` [.., E]: ``routed_scale * s_e / sum_{j in S} s_j`` for the
    ``top_k`` experts of ``S`` and 0 elsewhere; ``S`` by ``top_k`` rounds
    of argmax over ``sigmoid(x W_r) + b`` (the lowest index wins a tie).
    The product is exact in float32 unless the weights are stored
    narrower.  Where the configuration does not train its routers, ``W_r``
    takes no gradient."""
    router = m["router"] if s.train_router \
        else jax.lax.stop_gradient(m["router"])
    scores = jax.nn.sigmoid(jnp.einsum(
        "...d,de->...e", x, router, preferred_element_type=jnp.float32))
    left = scores + jax.lax.stop_gradient(
        m["router_bias"].astype(jnp.float32))
    chosen = jnp.zeros(scores.shape, bool)
    for _ in range(s.top_k):
        best = jax.nn.one_hot(jnp.argmax(left, -1), scores.shape[-1],
                              dtype=bool)
        chosen |= best
        left = jnp.where(best, -jnp.inf, left)
    picked = jnp.where(chosen, scores, 0.0)
    return s.routed_scale * picked / picked.sum(-1, keepdims=True)


def moe_ffn(x, m, products=None, *, s: Settings):
    """``Shared(x) + sum over the held experts of S`` (see the module's
    docstring): every held expert applied to every token, weighted by
    ``g`` (0 where it was not chosen)."""
    held = m["experts"]["w_gate"].shape[0]
    g = expert_weights(x, m, s)[..., s.first_held:s.first_held + held]

    @jax.checkpoint
    def one(y, expert):
        w, g_e = expert
        return y + g_e[..., None].astype(x.dtype) \
            * _swiglu(x, w, products), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (m["experts"], jnp.moveaxis(g, -1, 0)))
    if "shared" in m:
        y = y + _swiglu(x, m["shared"], products)
    return y


def _layer(x, lp, s: Settings, products):
    x = x + _latent_attention(
        _rms_norm(x, lp["ln_attn"]["scale"], s.eps), lp["attn"], s,
        products)
    h = _rms_norm(x, lp["ln_mlp"]["scale"], s.eps)
    if "mlp" in lp:
        return x + _swiglu(h, lp["mlp"], products)
    return x + moe_ffn(h, lp["moe"], products, s=s)


def to_reference(params) -> dict:
    """The reference keeps the program-named tree as it is."""
    return params


def features(ref, tokens, compute="float32", *, s: Settings):
    """Final-norm activations [B, T, D], in the storage type."""
    dtype, products = PRECISIONS[_name(compute)]
    ref = jax.tree_util.tree_map(lambda a: a.astype(dtype), ref)
    x = ref["embed"][tokens]
    for i in range(sum(k.startswith("layers_") for k in ref)):
        lp = ref[f"layers_{i}"]
        # one sequence at a time, each recomputed in the backward pass
        x = jax.lax.map(jax.checkpoint(
            lambda row, lp=lp: _layer(row[None], lp, s, products)[0]), x)
    return _rms_norm(x, ref["ln_final"]["scale"], s.eps), ref["head"], \
        products


def sum_loss_fn(ref, tokens, compute="float32", scale=1.0, *,
                s: Settings):
    """SUM over rows and positions of the next-token cross entropy times
    ``scale``, the head one sequence at a time."""
    x, head, products = features(ref, tokens, compute, s=s)

    @jax.checkpoint
    def row(args):
        x_row, t_row = args
        logits = _mm("td,vd->tv", x_row[:-1], head, products, (-1,),
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


@jax.jit
def leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(a.astype(jnp.float32) ** 2)), tree)


@jax.jit
def leaf_diff_norms(a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.sqrt(jnp.sum(
            (x.astype(jnp.float32) - y.astype(jnp.float32)) ** 2)), a, b)


@functools.partial(jax.jit, static_argnames=("seed",))
def sample_elements(tree, seed: int):
    """``gpt2.SAMPLE`` elements of every leaf, evenly strided from an
    offset the seed picks."""
    return jax.tree_util.tree_map(
        lambda a: a.reshape(-1)[_sample_idx(a.size, seed)].astype(
            jnp.float32), tree)


def _flat(tree, convert) -> dict:
    return {"/".join(str(getattr(k, "key", k)) for k in path): convert(leaf)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def flatten(tree) -> dict:
    """``{"a/b/c": float}`` from a tree of scalar norms."""
    return _flat(tree, float)


def flatten_samples(tree) -> dict:
    import numpy as np

    return _flat(tree, np.asarray)


def train_steps(ref, batches, *, row_block=1, compute="float32",
                sample_seed=0, s: Settings):
    """``gpt2.train_steps`` for this model: the loss of every step, the
    per-leaf norm of the first gradient, the per-leaf norm of the
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
    ``config_path``: a module ``reference/<name>.py`` puts them into its
    own namespace, and a configuration names that module."""
    with open(config_path) as f:
        s = Settings.from_config(json.load(f))
    out = {name: functools.partial(globals()[name], s=s)
           for name in ("train_steps", "lowered_block_grad")}
    out.update(SETTINGS=s, to_reference=to_reference, flatten=flatten,
               flatten_samples=flatten_samples, leaf_norms=leaf_norms,
               leaf_diff_norms=leaf_diff_norms,
               sample_elements=sample_elements)
    return out
