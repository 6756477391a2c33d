"""The plain language model of Qwen3-Next (``model_type: qwen3_next``):
forward pass, loss, gradients and AdamW in ``jax.numpy``, for the whole
block and for ONE CHIP'S SHARE of it under expert parallelism.

``RMS0(x; w) = x / rms(x) * (1 + w)`` is the model's zero-centred norm.
Layer ``i`` is FULL where ``(i + 1) % full_attention_interval == 0``, else
LINEAR; ``x [T, D]`` enters it:

    u = x + Mixer(RMS0(x; w_in))          x' = u + MoE(RMS0(u; w_post))

    LINEAR (Gated DeltaNet; Hk key heads, Hv = r Hk value heads of Dl;
    value heads r j .. r j + r - 1 read key head j), h = RMS0(x):
      [q k v z] = h W_qkvz   by key head: Dl of q, Dl of k, r Dl of v, of z
      [b a]     = h W_ba     by key head: r of b, r of a
      c_t = silu(sum_{i<K} w_conv[:, i] * [q k v]_{t-K+1+i})   depthwise over
            the channels of q | k | v, causal, zeros before the sequence
      beta_t = sigmoid(b_t)
      g_t = -exp(A_log) softplus(a_t + dt_bias)       float32, a value head
      q_t = l2norm(q_t) / sqrt(Dl), k_t = l2norm(k_t)          eps 1e-6
      S_t = exp(g_t) S_{t-1};  d_t = beta_t (v_t - S_t^T k_t);
      S_t += k_t d_t^T;  o_t = S_t^T q_t         S [Dl, Dl] float32, S_0 = 0
      y = (rmsnorm(o_t; w_n) * silu(z_t)) W_out

    FULL (H query heads over G key/value heads of Dh), h = RMS0(x):
      [q gate] = h W_q  by head;  k = h W_k;  v = h W_v
      q, k = RMS0 over Dh, then rotary at theta on the first
             partial_rotary_factor * Dh columns (halves against each other)
      a_t = sum_{s<=t} softmax_s(q_t . k_s / sqrt(Dh)) v_s
      y = (concat(a) * sigmoid(gate)) W_o

    MoE, m = RMS0(u):
      p = softmax(m W_r) float32;  S = the top-k;  g_e = p_e / sum_S p
      MoE(m) = sigmoid(m w_sg) Shared(m) + sum_{e in S, held} g_e E_e(m)

    end  RMS0, logits = x W_head, mean next-token cross entropy

``A_log = a_log + log(16 (h + 1/2) / Hv)`` for value head ``h``
(``_decay_offsets``; the configuration's ``assumed`` says why): the leaf is
the part training moves.  The recurrence runs TOKEN BY TOKEN under
``lax.scan``: no chunk, no triangular solve, no kernel; ``TOKENS`` of them
make one block that the backward recomputes, so that it holds one state a
block and not one a token.  Its two products with the state, ``S^T k`` and
``S^T q``, take their operands as every product does (rounded to bfloat16
at the stated precision); the decay, the outer product ``k d^T`` and the
state are float32 in every precision.  It imports nothing of the program
and none of its mechanisms: the scores of ALL pairs are formed, ``ROWS``
queries at a time; every held expert is applied to every token and
weighted by a mask; the convolution is written over the concatenated
channels.  The gradients are the cross-entropy's with ``S`` held constant.

Weights by the program's names:

    embed, head [V, D], ln_final/w, layers_<i>/{ln_in/w, ln_post/w,
    gdn/{w_qkvz [D,Hk,2Dl(1+r)], w_ba [D,Hk,2r], conv [2Hk Dl + Hv Dl, K],
         a_log [Hv], dt_bias/scale [Hv], norm/scale [Dl], w_out [Hv,Dl,D]}
    | attn/{wq [D,H,2Dh], wk, wv [D,G,Dh], q_norm/w, k_norm/w [Dh],
            wo [H,Dh,D]},
    moe/{router [D,E], shared_gate [D,1], shared/{w_gate,w_up [D,Fs],
         w_down [Fs,D]}, experts/{w_gate,w_up [held,D,F], w_down [held,F,D]}}}
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
)
from benchmark.reference.gpt2 import (
    PRECISIONS,
    _accumulate,
    _name,
    adamw_update,
)
from benchmark.reference.keye_vl2 import _rotary     # rotate_half
from benchmark.reference.smallthinker import expert_weights

ROWS = 256      # query rows of one block of the attention over all pairs
TOKENS = 64     # tokens of one recomputed block of the recurrence
SLAB = 2048     # tokens the experts and the head take at a time


@dataclasses.dataclass(frozen=True)
class Settings:
    """What the weights' shapes do not say.  ``wrong``: a model that is NOT
    the configuration's, for the tests and the chip's controls that must
    read not correct (``no_decay``: g = 0; ``beta_one``: beta = 1;
    ``no_conv``: the convolution and its silu left out; ``rotary_all``: the
    rotary over every column of a head)."""
    top_k: int
    first_held: int
    full_interval: int
    rotary: int
    theta: float
    eps: float
    train_router: bool = True
    wrong: str = ""

    @classmethod
    def from_config(cls, cfg: dict) -> "Settings":
        return cls(top_k=int(cfg["num_experts_per_tok"]),
                   first_held=int(cfg["deployment"]["experts_held"][0]),
                   full_interval=int(cfg["full_attention_interval"]),
                   rotary=int(cfg["partial_rotary_factor"]
                              * cfg["head_dim"]),
                   theta=float(cfg["rope_theta"]),
                   eps=float(cfg["rms_norm_eps"]),
                   train_router=bool(cfg.get("train_router", True)),
                   wrong=str(cfg.get("wrong", "")))


def _rms0(x, p, eps):
    return _rms_norm(x, 1.0 + p["w"].astype(jnp.float32), eps)


def _l2norm(x):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, -1, keepdims=True) + 1e-6)


def _decay_offsets(heads: int):
    return jnp.log(16.0 * (jnp.arange(heads, dtype=jnp.float32) + 0.5)
                   / heads)


def _recurrence(q, k, v, g, beta, products):
    """``o [T, Hv, Dv]`` of ``q, k [T, Hv, Dk]``, ``v [T, Hv, Dv]``, ``g,
    beta [T, Hv]``, all float32: one token at a time."""
    t = q.shape[0]

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[:, None, None]
        d = b_t[:, None] * (v_t - _mm("hkv,hk->hv", s, k_t, products))
        s = s + k_t[:, :, None] * d[:, None, :]
        return s, _mm("hkv,hk->hv", s, q_t, products)

    @jax.checkpoint
    def block(s, xs):
        return jax.lax.scan(token, s, xs)

    size = TOKENS if t % TOKENS == 0 else t
    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    xs = tuple(x.reshape((t // size, size) + x.shape[1:])
               for x in (q, k, v, g, beta))
    return jax.lax.scan(block, s0, xs)[1].reshape(v.shape)


def _linear_mixer(h, p, s: Settings, products):
    """``h [T, D]``, one sequence, in three parts the backward recomputes
    one after the other (what each holds for it is never held beside the
    others'): the operands of the recurrence, the recurrence, the gated
    norm and ``W_out``."""
    t = h.shape[0]
    hk, hv = p["w_qkvz"].shape[1], p["a_log"].shape[0]
    r, dl = hv // hk, p["norm"]["scale"].shape[0]

    @jax.checkpoint
    def operands(h, p):
        qkvz = _mm("td,dhc->thc", h, p["w_qkvz"], products, (-1,), (0,))
        ba = _mm("td,dhc->thc", h, p["w_ba"], products, (-1,), (0,))
        q, k, v, z = jnp.split(qkvz, (dl, 2 * dl, (2 + r) * dl), axis=-1)
        # the channels of q | k | v side by side, as the convolution sees
        # them
        mixed = jnp.concatenate([y.reshape(t, -1) for y in (q, k, v)],
                                axis=-1)
        if s.wrong != "no_conv":
            taps = p["conv"].shape[-1]
            padded = jnp.pad(mixed, ((taps - 1, 0), (0, 0)))
            mixed = jax.nn.silu(sum(padded[i:i + t] * p["conv"][:, i]
                                    for i in range(taps)))
        q, k, v = jnp.split(mixed, (hk * dl, 2 * hk * dl), axis=-1)
        q, k = (jnp.repeat(_l2norm(y.reshape(t, hk, dl)), r, axis=1)
                for y in (q, k))
        b, a = (ba[..., :r].reshape(t, hv).astype(jnp.float32),
                ba[..., r:].reshape(t, hv).astype(jnp.float32))
        beta = jnp.ones_like(b) if s.wrong == "beta_one" \
            else jax.nn.sigmoid(b)
        g = -jnp.exp(p["a_log"].astype(jnp.float32) + _decay_offsets(hv)) \
            * jax.nn.softplus(a + p["dt_bias"]["scale"].astype(jnp.float32))
        if s.wrong == "no_decay":
            g = jnp.zeros_like(g)
        return (q / dl ** 0.5, k, v.reshape(t, hv, dl).astype(jnp.float32),
                g, beta), z.reshape(t, hv, dl)

    @jax.checkpoint
    def out(o, z, p):
        y = _rms_norm(o, p["norm"]["scale"], s.eps) * jax.nn.silu(z)
        return _mm("thv,hvd->td", y, p["w_out"], products, (-2, -1), (0, 1))

    recurrent, z = operands(h, p)
    o = jax.checkpoint(functools.partial(_recurrence, products=products))(
        *recurrent)
    return out(o.astype(h.dtype), z, p)


def _full_mixer(h, a, s: Settings, products):
    """``h [T, D]``, one sequence: all pairs' scores, ``ROWS`` queries at a
    time, each block recomputed in the backward."""
    t = h.shape[0]
    heads, groups, dh = a["wq"].shape[1], a["wk"].shape[1], a["wk"].shape[2]
    every = jnp.arange(t)
    turned = dh if s.wrong == "rotary_all" else s.rotary

    def positions(y, pos):
        return jnp.concatenate([_rotary(y[..., :turned], s.theta, pos),
                                y[..., turned:]], axis=-1)

    k = positions(_rms0(_mm("td,dgk->tgk", h, a["wk"], products, (-1,),
                            (0,)), a["k_norm"], s.eps), every)
    v = _mm("td,dgk->tgk", h, a["wv"], products, (-1,), (0,))
    k, v = (jnp.repeat(y, heads // groups, axis=1) for y in (k, v))
    rows = ROWS if t % ROWS == 0 else t

    @jax.checkpoint
    def block(start):
        pos = start + jnp.arange(rows)
        hq = jax.lax.dynamic_slice_in_dim(h, start, rows, axis=0)
        q, gate = jnp.split(_mm("td,dhc->thc", hq, a["wq"], products, (-1,),
                                (0,)), 2, axis=-1)
        q = positions(_rms0(q, a["q_norm"], s.eps), pos)
        logits = _mm("qhd,khd->hqk", q, k, products) / (dh ** 0.5)
        logits = jnp.where((every[None, :] <= pos[:, None])[None],
                           logits.astype(jnp.float32), -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(h.dtype)
        return _mm("hqk,khd->qhd", probs, v, products) * jax.nn.sigmoid(gate)

    o = jax.lax.map(block, jnp.arange(0, t, rows)).reshape(t, heads, dh)
    return _mm("thv,hvd->td", o, a["wo"], products, (-2, -1), (0, 1))


def _swiglu(x, w, products):
    gate = _mm("...d,df->...f", x, w["w_gate"], products, (-1,), (0,))
    up = _mm("...d,df->...f", x, w["w_up"], products, (-1,), (0,))
    return _mm("...f,fd->...d", jax.nn.silu(gate) * up, w["w_down"],
               products, (-1,), (0,))


def experts(x, m, products=None, *, s: Settings):
    """The gated shared expert, and the held experts of ``S``, each applied
    to every token of ``x`` and weighted by ``g`` (0 where not chosen)."""
    held = m["experts"]["w_gate"].shape[0]
    g = expert_weights(x, m, s)[..., s.first_held:s.first_held + held]

    @jax.checkpoint
    def one(y, expert):
        w, g_e = expert
        return y + g_e[..., None].astype(x.dtype) \
            * _swiglu(x, w, products), None

    shared = jax.nn.sigmoid(_mm(
        "...d,do->...o", x, m["shared_gate"], products, (-1,), (0,))) \
        * _swiglu(x, m["shared"], products)
    return jax.lax.scan(one, shared, (m["experts"],
                                      jnp.moveaxis(g, -1, 0)))[0]


def _slabs(fn, *rows):
    """``fn`` over ``SLAB`` rows of every array at a time, each slab
    recomputed in the backward (no token reads another here): what is as
    long as the sequence is then the stream alone."""
    t = rows[0].shape[0]
    size = SLAB if t % SLAB == 0 else t
    out = jax.lax.map(jax.checkpoint(lambda xs: fn(*xs)), tuple(
        x.reshape((t // size, size) + x.shape[1:]) for x in rows))
    return out.reshape((t,) + out.shape[2:])


def _layer(x, lp, full: bool, s: Settings, products):
    h = _rms0(x, lp["ln_in"], s.eps)
    u = x + (_full_mixer(h, lp["attn"], s, products) if full
             else _linear_mixer(h, lp["gdn"], s, products))
    return _slabs(lambda v: v + experts(
        _rms0(v, lp["ln_post"], s.eps), lp["moe"], products, s=s), u)


def is_full(i: int, s: Settings) -> bool:
    return (i + 1) % s.full_interval == 0


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
            lambda row, lp=lp, full=is_full(i, s): _layer(
                row, lp, full, s, products)), x)
    x = _rms0(x, ref["ln_final"], s.eps)

    def losses(x_rows, next_tokens):
        logits = _mm("td,vd->tv", x_rows, ref["head"], products, (-1,),
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


def to_reference(params) -> dict:
    """The program-named tree as it is, on the HOST: the caller holds it
    through all the steps, and at the cell's size a copy of the weights
    that stays in the chip's memory beside the parameters, both moments
    and the gradient is the copy that does not fit."""
    return jax.device_get(params)


def train_steps(ref, batches, *, row_block=1, compute="float32",
                sample_seed=0, s: Settings):
    """``deepseek_v3.train_steps`` for this model: the loss of every step,
    the per-leaf norm of the first gradient, the per-leaf norm of the
    parameters' change after the last step, and the sampled elements of
    the first gradient.  ``ref`` (:func:`to_reference`'s) goes to the
    device for the steps and again, once the gradients are gone, for the
    change."""
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
