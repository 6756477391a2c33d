"""The plain language model of LFM2-MoE (``model_type: lfm2_moe``): forward
pass, loss, gradients and AdamW in ``jax.numpy``, for the whole block and
for ONE CHIP'S SHARE of it under expert parallelism.

``RMS(x; w) = x / rms(x) * w`` (a plain weight, eps ``norm_eps``).  Layer
``l`` is what ``layer_types[l]`` says; ``x [T, D]`` enters it:

    h = x + Op_l(RMS(x; w_op))            y = h + FFN_l(RMS(h; w_ffn))

    conv (the gated short convolution, K taps, no bias, no activation),
    u = RMS(x):
      [B C X] = u W_in          three chunks of D columns, in that order
      z_t = B_t * X_t
      c_t = sum_{i<K} w[:, i] z_{t-K+1+i}     depthwise over the D channels,
                                causal, zeros before the sequence
      Op = (C * c) W_out

    full_attention (H query heads over G key/value heads of Dh), u = RMS(x):
      q = u W_q;  k = u W_k;  v = u W_v
      q, k = RMS over Dh (plain weight), then rotary at theta over ALL Dh
             columns (halves against each other)
      a_t = sum_{s<=t} softmax_s(q_t . k_s / sqrt(Dh)) v_s;  query head j
            reads key/value head j // (H // G)
      Op = concat(a) W_o

    FFN, l < num_dense_layers:  (silu(m W_1) * (m W_3)) W_2
    FFN, the others, m = RMS(h):
      s = sigmoid(m W_r) float32;  S = the top-k of s + b (b: no gradient)
      g_e = routed_scale * s_e / (sum_{j in S} s_j + 1e-6)
      FFN(m) = sum_{e in S, held} g_e E_e(m)           no shared expert

    end  RMS, logits = x W_head (W_head the embedding table where the tree
         has no ``head``), mean next-token cross entropy

It imports nothing of the program and none of its mechanisms: the
convolution is a sum over K copies of ``z``, each moved down by its lag;
the scores of ALL pairs are formed, ``ROWS`` queries at a time; every held
expert is applied to every token and weighted by a mask.  ``_slabs`` (2,048
tokens of an FFN or of the head at a time) and the tree kept on the host
are ``qwen3_next.py``'s.

Weights by the program's names:

    embed [V, D], (head [V, D]), ln_final/scale, layers_<i>/{ln_op/scale,
    ln_ffn/scale,
    conv/{w_in [D, 3D], taps [D, K], w_out [D, D]}
    | attn/{wq [D,H,Dh], wk, wv [D,G,Dh], q_norm/scale, k_norm/scale [Dh],
            wo [H,Dh,D]},
    ffn/{w_gate, w_up [D, F], w_down [F, D]}
    | moe/{router [D,E], router_bias [E],
           experts/{w_gate,w_up [held,D,Fe], w_down [held,Fe,D]}}}
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
)
from benchmark.reference.gpt2 import (
    PRECISIONS,
    _accumulate,
    _name,
    adamw_update,
)
from benchmark.reference.keye_vl2 import _exact, _rotary     # rotate_half
from benchmark.reference.qwen3_next import _slabs, to_reference  # noqa: F401

ROWS = 256      # query rows of one block of the attention over all pairs
NORM_EPS = 1e-6     # what the picks' scores' sum is raised by


@dataclasses.dataclass(frozen=True)
class Settings:
    """What the weights' shapes do not say.  ``wrong``: a model that is NOT
    the configuration's (``no_conv``: c = z; ``no_b_gate``: z = X;
    ``no_qk_norm``: q and k go to the rotary as projected; ``no_rotary``:
    no position reaches a head), handed in by the tests and by
    ``benchmark/wrong_models.py``, which must read not correct; no
    configuration can ask for one (:meth:`from_config` refuses the key)."""
    top_k: int
    first_held: int
    layer_types: tuple
    num_dense_layers: int
    theta: float
    eps: float
    routed_scale: float = 1.0
    train_router: bool = True
    embed_scale: float = 1.0
    wrong: str = ""

    @classmethod
    def from_config(cls, cfg: dict) -> "Settings":
        if "wrong" in cfg:
            raise ValueError("a configuration's reference is the stated "
                             "model: the key 'wrong' is not one of its")
        return cls(top_k=int(cfg["num_experts_per_tok"]),
                   first_held=int(cfg["deployment"]["experts_held"][0]),
                   layer_types=tuple(cfg["layer_types"]),
                   num_dense_layers=int(cfg["num_dense_layers"]),
                   theta=float(cfg["rope_theta"]),
                   eps=float(cfg["norm_eps"]),
                   routed_scale=float(cfg["routed_scaling_factor"]),
                   train_router=bool(cfg.get("train_router", True)),
                   embed_scale=float(cfg.get("embed_scale", 1.0)))


def short_conv(z, taps):
    """``c_t = sum_i taps[:, i] z_{t-K+1+i}``, ``z [T, D]``, ``taps [D,
    K]``: tap ``i`` reads the token ``K - 1 - i`` places back, which is
    ``z`` moved down by that many rows with zeros let in above."""
    t, k = z.shape[0], taps.shape[-1]
    out = jnp.zeros_like(z)
    for i in range(k):
        lag = k - 1 - i
        moved = jnp.concatenate(
            [jnp.zeros((lag,) + z.shape[1:], z.dtype), z[:t - lag]])
        out = out + moved * taps[:, i].astype(z.dtype)
    return out


def _conv_mixer(u, p, s: Settings, products):
    """``u [T, D]``, one sequence."""
    d = u.shape[-1]
    bcx = _mm("td,dc->tc", u, p["w_in"], products, (-1,), (0,))
    b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = x if s.wrong == "no_b_gate" else b * x
    mixed = z if s.wrong == "no_conv" else short_conv(z, p["taps"])
    return _mm("tc,cd->td", c * mixed, p["w_out"], products, (-1,), (0,))


def _attention_mixer(u, a, s: Settings, products):
    """``u [T, D]``, one sequence: all pairs' scores, ``ROWS`` queries at a
    time, each block recomputed in the backward."""
    t = u.shape[0]
    heads, groups, dh = a["wq"].shape[1], a["wk"].shape[1], a["wk"].shape[2]
    every = jnp.arange(t)

    def head_form(y, norm, pos):
        if s.wrong != "no_qk_norm":
            y = _rms_norm(y, norm["scale"], s.eps)
        return y if s.wrong == "no_rotary" else _rotary(y, s.theta, pos)

    k = head_form(_mm("td,dgk->tgk", u, a["wk"], products, (-1,), (0,)),
                  a["k_norm"], every)
    v = _mm("td,dgk->tgk", u, a["wv"], products, (-1,), (0,))
    k, v = (jnp.repeat(y, heads // groups, axis=1) for y in (k, v))
    rows = ROWS if t % ROWS == 0 else t

    @jax.checkpoint
    def block(start):
        pos = start + jnp.arange(rows)
        uq = jax.lax.dynamic_slice_in_dim(u, start, rows, axis=0)
        q = head_form(_mm("td,dhk->thk", uq, a["wq"], products, (-1,),
                          (0,)), a["q_norm"], pos)
        logits = _mm("qhd,khd->hqk", q, k, products) / (dh ** 0.5)
        logits = jnp.where((every[None, :] <= pos[:, None])[None],
                           logits.astype(jnp.float32), -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(u.dtype)
        return _mm("hqk,khd->qhd", probs, v, products)

    o = jax.lax.map(block, jnp.arange(0, t, rows)).reshape(t, heads, dh)
    return _mm("thv,hvd->td", o, a["wo"], products, (-2, -1), (0, 1))


def expert_weights(x, m, s: Settings):
    """``g [.., E]``: ``routed_scale * s_e / (sum_{j in S} s_j + 1e-6)`` for
    the ``top_k`` experts of ``S`` and 0 elsewhere; ``S`` by ``top_k``
    rounds of argmax over ``sigmoid(x W_r) + b`` (the lowest index wins a
    tie).  The product is exact in float32 unless the weights are stored
    narrower.  Where the configuration does not train its routers, ``W_r``
    takes no gradient; ``b`` never does."""
    router = m["router"] if s.train_router \
        else jax.lax.stop_gradient(m["router"])
    scores = jax.nn.sigmoid(_exact("...d,de->...e", x, router))
    left = scores + jax.lax.stop_gradient(
        m["router_bias"].astype(jnp.float32))
    chosen = jnp.zeros(scores.shape, bool)
    for _ in range(s.top_k):
        best = jax.nn.one_hot(jnp.argmax(left, -1), scores.shape[-1],
                              dtype=bool)
        chosen |= best
        left = jnp.where(best, -jnp.inf, left)
    picked = jnp.where(chosen, scores, 0.0)
    return s.routed_scale * picked / (picked.sum(-1, keepdims=True)
                                      + NORM_EPS)


def experts(x, m, products=None, *, s: Settings):
    """The held experts of ``S``, each applied to every token of ``x`` and
    weighted by ``g`` (0 where not chosen); nothing is shared."""
    held = m["experts"]["w_gate"].shape[0]
    g = expert_weights(x, m, s)[..., s.first_held:s.first_held + held]

    @jax.checkpoint
    def one(y, expert):
        w, g_e = expert
        return y + g_e[..., None].astype(x.dtype) \
            * _swiglu(x, w, products), None

    return jax.lax.scan(one, jnp.zeros_like(x), (
        m["experts"], jnp.moveaxis(g, -1, 0)))[0]


def _layer(x, lp, kind: str, dense: bool, s: Settings, products):
    u = _rms_norm(x, lp["ln_op"]["scale"], s.eps)
    h = x + (_conv_mixer(u, lp["conv"], s, products) if kind == "conv"
             else _attention_mixer(u, lp["attn"], s, products))

    def ffn(v):
        m = _rms_norm(v, lp["ln_ffn"]["scale"], s.eps)
        return v + (_swiglu(m, lp["ffn"], products) if dense
                    else experts(m, lp["moe"], products, s=s))

    return _slabs(ffn, h)


def sum_loss_fn(ref, tokens, compute="float32", scale=1.0, *, s: Settings):
    """SUM over rows and positions of the next-token cross entropy times
    ``scale``; layers and head one sequence at a time, each recomputed in
    the backward."""
    dtype, products = PRECISIONS[_name(compute)]
    ref = jax.tree_util.tree_map(lambda a: a.astype(dtype), ref)
    x = ref["embed"][tokens] * jnp.asarray(s.embed_scale, dtype)
    for i, kind in enumerate(s.layer_types):
        lp = ref[f"layers_{i}"]
        x = jax.lax.map(jax.checkpoint(
            lambda row, lp=lp, kind=kind, dense=i < s.num_dense_layers:
            _layer(row, lp, kind, dense, s, products)), x)
    x = _rms_norm(x, ref["ln_final"]["scale"], s.eps)
    head = ref["head"] if "head" in ref else ref["embed"]

    def losses(x_rows, next_tokens):
        logits = _mm("td,vd->tv", x_rows, head, products, (-1,),
                     (1,)).astype(jnp.float32)
        picked = jnp.take_along_axis(logits, next_tokens[:, None], -1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    def row(args):
        x_row, t_row = args
        # the last position predicts nothing: its loss is left out
        return jnp.sum(_slabs(losses, x_row, jnp.roll(t_row, -1))[:-1])

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
    p = jax.device_put(p0)
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
