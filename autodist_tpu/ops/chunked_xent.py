"""Chunked-vocabulary softmax cross entropy: O(N·chunk) logits memory.

Motivation (measured, BASELINE.md): lm1b's 793k-word softmax makes the
``[tokens, vocab]`` logits tensor the training bound — 16 GB at batch
256 — and the reference hit the same wall (its lm1b used a *sampled*
softmax, ``examples/lm1b/language_model.py``, trading accuracy for
memory).  TPU-natively the exact loss is computable without ever
materializing full logits: stream the vocabulary in chunks through the
MXU, carrying running ``(max, sumexp, target_logit)`` — the same
streaming-softmax algebra as flash attention, applied to the output
projection.

* forward: one ``lax.scan`` over vocab chunks; per chunk an ``[N, C]``
  matmul in fp32, folded into the running stats and discarded.
* backward (custom VJP): a second scan recomputes each chunk's softmax
  probabilities from the saved row stats and accumulates ``dh`` and the
  (unavoidable, gradient-sized) ``dW``.

Peak extra memory: ``N·chunk`` fp32 instead of ``N·V`` logits.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


# Padded table rows (vocab not a multiple of chunk) are masked to this
# finite floor: exp(floor − m) underflows to exactly 0, and unlike −inf it
# cannot produce NaNs in max/sub arithmetic.
_MASKED = -1e30


def _stats_scan(h, w, chunk, valid_v):
    """Running (max, sumexp) stats over vocab chunks.  ``w`` is already
    padded to a chunk multiple; columns ≥ ``valid_v`` are masked out.
    Returns (m, s): per-row max [N] and sum-exp [N] with logits in fp32."""
    n = h.shape[0]
    nc = w.shape[0] // chunk
    wc = w.reshape(nc, chunk, w.shape[1])

    def step(carry, args):
        c_idx, w_c = args
        m, s = carry
        logits = jnp.dot(h, w_c.T, preferred_element_type=jnp.float32)
        col = c_idx * chunk + jnp.arange(chunk)
        logits = jnp.where(col[None, :] < valid_v, logits, _MASKED)
        m_c = jnp.max(logits, axis=1)
        m_new = jnp.maximum(m, m_c)
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=1)
        return (m_new, s), None

    init = (jnp.full((n,), _MASKED, jnp.float32),
            jnp.zeros((n,), jnp.float32))
    (m, s), _ = lax.scan(step, init, (jnp.arange(nc), wc))
    return m, s


def _target_logits(h, w, labels):
    """Per-row logit of the label class: a gather of W rows, no big matmul."""
    w_y = jnp.take(w, labels, axis=0)                      # [N, E]
    return jnp.sum(h.astype(jnp.float32) * w_y.astype(jnp.float32), axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _xent_rows(h, w, labels, chunk, valid_v):
    return _xent_rows_fwd(h, w, labels, chunk, valid_v)[0]


def _xent_rows_fwd(h, w, labels, chunk, valid_v):
    m, s = _stats_scan(h, w, chunk, valid_v)
    losses = (jnp.log(s) + m) - _target_logits(h, w, labels)
    return losses, (h, w, labels, m, s)


def _xent_rows_bwd(chunk, valid_v, res, g):
    """d loss_i / d logits_ic = softmax_ic − 1[c == labels_i]; recompute
    softmax per chunk from the saved row stats (logZ = m + log s)."""
    h, w, labels, m, s = res
    nc = w.shape[0] // chunk
    wc = w.reshape(nc, chunk, w.shape[1])
    logz = m + jnp.log(s)                                   # [N]
    gh32 = (g.astype(jnp.float32))[:, None]                 # [N, 1]
    h32 = h.astype(jnp.float32)

    def step(dh, args):
        c_idx, w_c = args
        logits = jnp.dot(h, w_c.T, preferred_element_type=jnp.float32)
        col = c_idx * chunk + jnp.arange(chunk)
        logits = jnp.where(col[None, :] < valid_v, logits, _MASKED)
        p = jnp.exp(logits - logz[:, None])                 # [N, C]; pad→0
        local = labels - c_idx * chunk
        onehot = (local[:, None] ==
                  jnp.arange(chunk)[None, :]).astype(jnp.float32)
        d = (p - onehot) * gh32                             # [N, C]
        dh = dh + jnp.dot(d, w_c.astype(jnp.float32))
        dw_c = jnp.dot(d.T, h32)                            # [C, E]
        return dh, dw_c

    dh, dwc = lax.scan(step, jnp.zeros_like(h, jnp.float32),
                       (jnp.arange(nc), wc))
    dw = dwc.reshape(w.shape)
    return dh.astype(h.dtype), dw.astype(w.dtype), None


_xent_rows.defvjp(_xent_rows_fwd, _xent_rows_bwd)


def chunked_softmax_cross_entropy(features: jax.Array, softmax_w: jax.Array,
                                  labels: jax.Array, *,
                                  chunk: int = 8192,
                                  weights: Optional[jax.Array] = None
                                  ) -> jax.Array:
    """Mean softmax cross entropy of ``features @ softmax_w.T`` against
    integer ``labels`` without materializing the logits.

    Args:
      features: ``[..., E]`` activations (any leading shape; flattened).
      softmax_w: ``[V, E]`` output-embedding table; any ``V`` — tables
        that don't divide into chunks are zero-padded and the pad columns
        masked out (their probabilities are exactly 0, their ``dW`` rows
        exactly 0, sliced away on return).
      labels: integer array matching ``features``'s leading shape.
      chunk: vocab rows per streamed block (``[N, chunk]`` fp32 is the
        peak logits footprint; keep it MXU-friendly — a multiple of 128).
      weights: a weight a row, shaped as ``labels`` (float32, no
        gradient): the result is ``sum_i w_i loss_i / N`` over ALL ``N``
        rows, and a row of weight zero adds nothing to ``dW`` or ``dh``
        (its cotangent is zero).  None: every row counts once.

    Exact (fp32 logit accumulation), unlike the reference's sampled
    softmax.  Matches ``cross_entropy_loss`` to fp32 tolerance.
    """
    e = features.shape[-1]
    h = features.reshape(-1, e)
    y = labels.reshape(-1).astype(jnp.int32)
    v = softmax_w.shape[0]
    chunk = min(chunk, v)
    vp = -(-v // chunk) * chunk
    w = softmax_w if vp == v else jnp.pad(softmax_w,
                                          ((0, vp - v), (0, 0)))
    losses = _xent_rows(h, w, y, chunk, v)
    if weights is not None:
        losses = losses * lax.stop_gradient(
            weights.reshape(-1).astype(jnp.float32))
    return jnp.mean(losses)
