"""Model zoo plumbing.

The reference ships its benchmark models as example scripts
(``examples/benchmark/imagenet.py`` — ResNet/VGG/DenseNet/Inception via
tf.keras.applications, ``examples/benchmark/bert.py``, ``examples/lm1b``,
NCF).  Here each model family is a first-class module exposing a
:class:`ModelSpec` that plugs straight into ``AutoDist.capture``:

    spec = resnet.resnet50(num_classes=1000)
    params = spec.init(jax.random.PRNGKey(0))
    ad.capture(params=params, optimizer=..., loss_fn=spec.loss_fn,
               sparse_vars=spec.sparse_vars)
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Sequence, Tuple

import jax
import numpy as np


@dataclass
class ModelSpec:
    """Everything AutoDist needs to distribute one model."""

    name: str
    init: Callable                 # rng -> params
    loss_fn: Callable              # (params, batch) -> scalar loss
    apply_fn: Callable             # (params, inputs) -> outputs (serving)
    make_batch: Callable           # (rng, batch_size) -> batch pytree
    # optional manual value-and-grad: (params, batch) -> (loss, grads);
    # when set, capture(grad_fn=spec.grad_fn) replaces autodiff (e.g. the
    # hand-scheduled 1F1B pipeline backward)
    grad_fn: Any = None
    sparse_vars: Tuple[str, ...] = ()
    untrainable_vars: Tuple[str, ...] = ()
    pipeline_vars: Tuple[str, ...] = ()  # leading dim = pipeline-stage axis
    expert_vars: Tuple[str, ...] = ()    # leading dim = MoE expert axis
    config: Dict[str, Any] = field(default_factory=dict)

    def sample_batch(self, batch_size: int, seed: int = 0):
        return self.make_batch(np.random.RandomState(seed), batch_size)


def layer_norm(x, scale, eps=1e-6) -> jax.Array:
    """Bias-free layer norm (matches flax ``nn.LayerNorm(use_bias=False)``)
    for the functional (non-flax) models."""
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale


def rms_norm(x, scale, eps=1e-6) -> jax.Array:
    """Root-mean-square norm (Zhang & Sennrich 2019): no mean taken off, no
    bias; the mean square is formed in float32 whatever ``x`` is stored
    in."""
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv * scale.astype(jnp.float32)).astype(x.dtype)


def _rotary(x: jax.Array, theta: float, split: Tuple[int, int],
            positions=None):
    """``x [B, T, ..., R]``, positions along axis 1: the pair ``(a, b)`` of
    position ``t`` and frequency ``i`` turns by ``t * theta^(-2i/R)``.
    ``split`` says where the last axis keeps its pairs: ``(-1, 2)``
    interleaved, ``(2, -1)`` as two halves.  ``positions [T]``: row ``j``
    of axis 1 stands at ``positions[j]`` and not at ``j``."""
    import jax.numpy as jnp

    t, r = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    at = jnp.arange(t, dtype=jnp.float32) if positions is None \
        else positions.astype(jnp.float32)
    angle = at[:, None] * inv                                      # [T,R/2]
    angle = angle.reshape((1, t) + (1,) * (x.ndim - 3) + (r // 2,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    axis = split.index(2) - 2
    parts = x.astype(jnp.float32).reshape(x.shape[:-1] + split)
    a, b = (jax.lax.index_in_dim(parts, i, axis, keepdims=False)
            for i in (0, 1))
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=axis)
    return out.reshape(x.shape).astype(x.dtype)


def rotary_halves(x: jax.Array, theta: float, positions=None) -> jax.Array:
    """Rotary positions on DE-INTERLEAVED columns: ``x[..., :R/2]`` holds
    the pairs' first members and ``x[..., R/2:]`` their second, and they
    come back so (the checkpoints' own code does this; where a weight's
    columns mean interleaved pairs ``(x[2i], x[2i+1])``, as kanana's do,
    the model gathers them to this order first).  The halves are taken as
    a DIMENSION of two, not as two slices: the compiler then folds the turn
    into the product that feeds it; sliced, each half is a 32-wide array
    padded to the chip's 128 lanes and crosses memory four times over.
    ``positions [T]``: where the rows of axis 1 stand, ``0 .. T - 1`` by
    default."""
    return _rotary(x, theta, (2, -1), positions)


def cross_entropy_loss(logits, labels, weights=None) -> jax.Array:
    """Mean softmax cross entropy with integer labels; with ``weights``
    (shaped as ``labels``, no gradient) each row's loss times its weight,
    over ALL rows."""
    import jax.numpy as jnp

    logz = jax.nn.log_softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logz.dtype)
    rows = jnp.sum(onehot * logz, axis=-1)
    if weights is not None:
        rows = rows * jax.lax.stop_gradient(weights.astype(rows.dtype))
    return -jnp.mean(rows)
