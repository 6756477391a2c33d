"""The plain GPT-2: forward pass, loss, gradients and AdamW in ``jax.numpy``.

Follows Radford et al. 2019 / the ``openai-community/gpt2-*`` config.json
(learned positions, pre-LayerNorm blocks, multi-head causal attention,
tanh GELU, final LayerNorm, head tied to the token embedding), with the
departures the configuration files list: no biases anywhere, LayerNorm
epsilon 1e-6, separate query/key/value matrices.

It imports nothing of the program.  It takes the weights the benchmark
made from the seed as a plain nested dict and reads them by the names
``transformer_lm`` gives them (that naming is the one thing the two share):

    embed [V, D], pos_embed [P, D],
    decoder/layers_<i>/{ln_attn/scale [D], attn/{query,key,value}/kernel
    [D, H, Dh], attn/out/kernel [H, Dh, D], ln_mlp/scale [D],
    mlp/wi/kernel [D, F], mlp/wo/kernel [F, D]}, decoder/ln_final/scale [D]

Inside, the layers are stacked ([L, ...] per leaf, :func:`to_reference`)
and walked by one ``lax.scan`` whose body is rematerialized: one layer is
compiled and one layer's activations are kept, so the reference is quick
to compile and small beside the program.  No kernel, no cache, no
batching tricks.

``compute`` names the precision, and a configuration's file states which
one is its reference and which its control (callers wrap every one in
``jax.default_matmul_precision("highest")``, so that nothing is rounded
but what is named here):

``"float32"``
    float32 storage, exact float32 products.
``"bfloat16_products"``
    float32 storage and sums; both operands of every matrix product, in
    the forward and in the backward pass, rounded to bfloat16 first.  This
    is what a TPU's default precision does to float32 operands (one
    bfloat16 pass), written out so that it means the same on any device.
``"bfloat16"``
    weights and activations stored in bfloat16: the nearest precision
    below either of the above.
``"int8"``
    every weight matrix rounded to int8 by output channel and every
    activation that enters it to int8 by token (symmetric, max / 127),
    products summed in float32, gradients passed straight through the
    rounding: the usual W8A8 scheme as fake quantization.

``tables`` is the type the two embedding tables are stored and summed in
where that differs from the rest (``transformer_lm(dtype=bfloat16)`` keeps
them, and only them, in bfloat16).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-6
ADAMW = dict(lr=1e-3, b1=0.9, b2=0.999, eps=1e-8, weight_decay=1e-4)


def _layer_norm(x, scale):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + LN_EPS)
            * scale.astype(jnp.float32)).astype(x.dtype)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _attention(q, k, v, products=None):
    """Causal softmax(QK^T / sqrt(d)) V on [B, T, H, Dh]."""
    t = q.shape[1]
    logits = _mm("bqhd,bkhd->bhqk", q, k, products) / (q.shape[-1] ** 0.5)
    mask = jnp.tril(jnp.ones((t, t), bool))
    logits = jnp.where(mask, logits.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return _mm("bhqk,bkhd->bqhd", probs, v, products)


def _round_bf16(x):
    """To the bfloat16 grid, kept in float32.  ``reduce_precision`` and
    not a pair of casts: XLA may drop a pair (PR 21 met that on the
    chip)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@jax.custom_vjp
def _operand(x):
    """An operand of a bfloat16 product: rounded on the way in, its
    cotangent untouched."""
    return _round_bf16(x)


_operand.defvjp(lambda x: (_round_bf16(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _result(y):
    """The result of a bfloat16 product: its cotangent is an operand of
    the backward pass's products, so it is rounded on the way back."""
    return y


_result.defvjp(lambda y: (y, None), lambda _, g: (_round_bf16(g),))


@jax.custom_vjp
def _stored_bf16(x):
    """A float32 value as a bfloat16 store keeps it: rounded, and its
    gradient rounded."""
    return _round_bf16(x)


_stored_bf16.defvjp(lambda x: (_round_bf16(x), None),
                    lambda _, g: (_round_bf16(g),))


def _q8(x, axes):
    """Round to the int8 grid along ``axes`` (symmetric, max / 127);
    the gradient passes straight through."""
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127.0 + 1e-30
    return x + jax.lax.stop_gradient(jnp.round(x / scale) * scale - x)


def _mm(eq, a, w, products, a_axes=None, w_axes=None):
    """``einsum(eq, a, w)`` with its operands rounded as ``products``
    says: ``"bfloat16"`` (both, forward and backward), ``"int8"`` (the
    activation over ``a_axes``, its contracted dims: one scale a token;
    the weight over ``w_axes``: one scale an output channel; the products
    of attention itself, which have no axes, stay exact) or None."""
    if products == "bfloat16":
        return _result(jnp.einsum(eq, _operand(a), _operand(w)))
    if products == "int8" and a_axes is not None:
        a, w = _q8(a, a_axes), _q8(w, w_axes)
    return jnp.einsum(eq, a, w)


def _block(x, lp, products=None):
    h = _layer_norm(x, lp["ln_attn"]["scale"])
    a = lp["attn"]
    q, k, v = (_mm("btd,dhk->bthk", h, a[n]["kernel"], products, (-1,), (0,))
               for n in ("query", "key", "value"))
    x = x + _mm("bthk,hkd->btd", _attention(q, k, v, products),
                a["out"]["kernel"], products, (-2, -1), (0, 1))
    h = _layer_norm(x, lp["ln_mlp"]["scale"])
    h = _gelu_tanh(_mm("btd,df->btf", h, lp["mlp"]["wi"]["kernel"], products,
                       (-1,), (0,)))
    return x + _mm("btf,fd->btd", h, lp["mlp"]["wo"]["kernel"], products,
                   (-1,), (0,))


def to_reference(params) -> dict:
    """The program-named tree as the reference keeps it: ``embed``,
    ``pos_embed``, ``ln_final`` and ``layers``, every layer leaf stacked
    to [L, ...]."""
    dec = params["decoder"]
    n_layers = len(dec) - 1
    layers = [dec[f"layers_{i}"] for i in range(n_layers)]
    return {"embed": params["embed"], "pos_embed": params["pos_embed"],
            "ln_final": dec["ln_final"]["scale"],
            "layers": jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *layers)}


#: compute -> (storage type, how the operands of a product are rounded)
PRECISIONS = {"float32": (jnp.float32, None),
              "bfloat16_products": (jnp.float32, "bfloat16"),
              "bfloat16": (jnp.bfloat16, None),
              "int8": (jnp.float32, "int8")}


def _name(compute) -> str:
    return compute if isinstance(compute, str) else jnp.dtype(compute).name


def logits_fn(ref, tokens, compute="float32", tables=None):
    """Logits [B, T, V] in float32 for ``tokens`` [B, T]; ``ref`` is a
    :func:`to_reference` tree."""
    dtype, products = PRECISIONS[_name(compute)]
    if tables is None:
        tables = jnp.result_type(ref["embed"], ref["pos_embed"])
    ref = jax.tree_util.tree_map(lambda a: a.astype(dtype), ref)
    embed, pos = ref["embed"], ref["pos_embed"][:tokens.shape[1]]
    if jnp.dtype(tables) == jnp.bfloat16 and dtype == jnp.float32:
        embed, pos = _stored_bf16(embed), _stored_bf16(pos)
        x = _stored_bf16(embed[tokens] + pos)
    else:
        x = embed[tokens] + pos
    x, _ = jax.lax.scan(
        jax.checkpoint(lambda x, lp: (_block(x, lp, products), None)),
        x, ref["layers"])
    x = _layer_norm(x, ref["ln_final"])
    return _mm("btd,vd->btv", x, embed, products, (-1,),
               (1,)).astype(jnp.float32)


def sum_loss_fn(params, tokens, compute="float32", tables=None, scale=1.0):
    """SUM over rows and positions of the next-token cross entropy times
    ``scale`` (so that row blocks add up; with ``scale`` 1 / (rows *
    (T - 1)) the blocks add up to the mean, and every cotangent of the
    backward pass is the mean's own, which matters once they are
    rounded)."""
    logits = logits_fn(params, tokens, compute, tables)[:, :-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.sum(logz - picked) * scale


@functools.partial(jax.jit, static_argnames=("compute", "tables", "scale"))
def _block_grad(params, tokens, compute, tables, scale):
    return jax.value_and_grad(
        lambda p: sum_loss_fn(p, tokens, compute, tables, scale))(params)


def lowered_block_grad(params, tokens, compute="float32") -> str:
    """The loss and gradients of one block of rows as lowered (StableHLO):
    what a control, put in the program's place, would show of its types."""
    return _block_grad.lower(params, tokens, _name(compute), None,
                             1.0).as_text()


@functools.partial(jax.jit, donate_argnums=(0,))
def _accumulate(acc, grads):
    return jax.tree_util.tree_map(
        lambda a, g: a + g.astype(jnp.float32), acc, grads)


def loss_and_grads(params, tokens, *, row_block=1, compute="float32",
                   tables=None):
    """Mean loss and its gradients over ``tokens`` [B, T], computed
    ``row_block`` rows at a time so that a batch the dense [B, H, T, T]
    attention could not hold still fits."""
    rows, t = tokens.shape
    total, acc = 0.0, None
    compute = _name(compute)
    tables = None if tables is None else jnp.dtype(tables).name
    for r in range(0, rows, row_block):
        loss, grads = _block_grad(params, tokens[r:r + row_block], compute,
                                  tables, 1.0 / (rows * (t - 1)))
        total = total + loss
        acc = (jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
               if acc is None else _accumulate(acc, grads))
    return total, acc


@functools.partial(jax.jit, donate_argnums=(1, 2, 3))
def adamw_update(params, grads, mu, nu, count):
    """optax.adamw(1e-3) written out: decoupled weight decay on every
    leaf, bias-corrected moments.  All state float32."""
    h = ADAMW
    count = count + 1
    mu = jax.tree_util.tree_map(
        lambda m, g: h["b1"] * m + (1 - h["b1"]) * g, mu, grads)
    nu = jax.tree_util.tree_map(
        lambda v, g: h["b2"] * v + (1 - h["b2"]) * g * g, nu, grads)
    c1 = 1 - h["b1"] ** count
    c2 = 1 - h["b2"] ** count

    def step(p, m, v):
        upd = (m / c1) / (jnp.sqrt(v / c2) + h["eps"]) \
            + h["weight_decay"] * p
        return p - h["lr"] * upd

    return jax.tree_util.tree_map(step, params, mu, nu), mu, nu, count


def _norm(a, per_layer):
    a = a.astype(jnp.float32) ** 2
    if per_layer:
        return jnp.sqrt(jnp.sum(a.reshape(a.shape[0], -1), axis=1))
    return jnp.sqrt(jnp.sum(a))


def _map_norm(fn, *trees):
    """``fn`` over leaves; leaves under ``layers`` (the stacked form) give
    one norm per layer."""
    return jax.tree_util.tree_map_with_path(
        lambda path, *xs: _norm(
            fn(*xs), str(getattr(path[0], "key", "")) == "layers"), *trees)


@jax.jit
def leaf_norms(tree):
    return _map_norm(lambda a: a, tree)


@jax.jit
def leaf_diff_norms(a, b):
    return _map_norm(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)


SAMPLE = 2048      # elements of every leaf compared one by one


def _sample_idx(size: int, seed: int):
    stride = max(1, size // SAMPLE)
    return (int(seed) % stride) + stride * jnp.arange(min(SAMPLE, size))


@functools.partial(jax.jit, static_argnames=("seed",))
def sample_elements(tree, seed: int):
    """A seeded sample of every leaf's elements: ``SAMPLE`` of them, evenly
    strided from an offset the seed picks (the same places in a program
    leaf and in its layer's slice of a stacked reference leaf)."""
    def pick(path, a):
        if str(getattr(path[0], "key", "")) == "layers":
            flat = a.reshape(a.shape[0], -1)
            return flat[:, _sample_idx(flat.shape[1], seed)].astype(
                jnp.float32)
        flat = a.reshape(-1)
        return flat[_sample_idx(flat.shape[0], seed)].astype(jnp.float32)
    return jax.tree_util.tree_map_with_path(pick, tree)


def flatten_samples(tree) -> dict:
    """``{"a/b/c": np.ndarray}`` by the program's names (see
    :func:`flatten`)."""
    import numpy as np

    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        keys = [str(getattr(k, "key", k)) for k in path]
        leaf = np.asarray(leaf)
        if keys[0] == "layers":
            for i, v in enumerate(leaf):
                out["/".join(["decoder", f"layers_{i}"] + keys[1:])] = v
        elif keys == ["ln_final"]:
            out["decoder/ln_final/scale"] = leaf
        else:
            out["/".join(keys)] = leaf
    return out


def train_steps(ref, batches, *, row_block=1, compute="float32",
                sample_seed=0):
    """Follow ``len(batches)`` AdamW steps from ``ref``, a
    :func:`to_reference` tree (float32 copies of its bfloat16 leaves are
    made; the caller's tree is untouched, and a caller short of memory
    drops the program-named tree it was made from first).  Returns the loss
    of every step, the per-leaf norm of the first gradient and the
    per-leaf norm of the parameters' change after the last step, each
    norm tree flattened to ``{path: float}``."""
    tables = jnp.result_type(ref["embed"], ref["pos_embed"])
    p0 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), ref)
    p = p0
    mu = jax.tree_util.tree_map(jnp.zeros_like, p0)
    nu = jax.tree_util.tree_map(jnp.zeros_like, p0)
    count = jnp.zeros((), jnp.int32)
    losses, first, sample = [], None, None
    for tokens in batches:
        loss, grads = loss_and_grads(p, tokens, row_block=row_block,
                                     compute=compute, tables=tables)
        if first is None:
            first = flatten(leaf_norms(grads))
            sample = flatten_samples(sample_elements(grads, sample_seed))
        p, mu, nu, count = adamw_update(p, grads, mu, nu, count)
        del grads
        losses.append(float(loss))
    return losses, first, flatten(leaf_diff_norms(p, p0)), sample


def flatten(tree) -> dict:
    """``{"a/b/c": float}`` by the program's names, from a tree of scalar
    norms (the program's own tree) or of per-layer norm vectors (a
    :func:`to_reference` tree, whose ``layers`` leaves are [L])."""
    import numpy as np

    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        keys = [str(getattr(k, "key", k)) for k in path]
        leaf = np.asarray(leaf)
        if keys[0] == "layers" and leaf.ndim == 1:
            for i, v in enumerate(leaf):
                out["/".join(["decoder", f"layers_{i}"] + keys[1:])] = \
                    float(v)
        elif keys == ["ln_final"]:
            out["decoder/ln_final/scale"] = float(leaf)
        else:
            out["/".join(keys)] = float(leaf)
    return out


@functools.partial(jax.jit, static_argnames=("compute",))
def first_choices(ref, tokens, compute="float32"):
    """The token this model puts first after every position of one
    sequence ``tokens`` [T] (pad the tail to a fixed T: attention is
    causal, so what follows a position does not reach it)."""
    return jnp.argmax(logits_fn(ref, tokens[None], compute)[0], -1)


@jax.jit
def gaps_below_best(ref, tokens, chosen):
    """Float32 pass over ``tokens`` [T]: how far the logit of
    ``chosen[t]`` lies below the best logit of row ``t`` (row ``t``
    chooses token ``t + 1``).  Returns [T] float32."""
    logits = logits_fn(ref, tokens[None])[0]
    got = jnp.take_along_axis(logits, chosen[:, None], -1)[:, 0]
    return logits.max(-1) - got
