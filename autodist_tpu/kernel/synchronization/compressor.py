"""Gradient compressors wrapping the data-axis all-reduce.

Parity: reference ``autodist/kernel/synchronization/compressor.py`` —
``NoneCompressor`` (:36-96, identity), ``HorovodCompressor`` (:146-176,
dtype-cast compression), ``HorovodCompressorEF`` (:208-284, error feedback),
``PowerSGDCompressor`` (commented out in the reference; implemented here as
a rank-r low-rank compressor since TPU matmuls make it cheap).

TPU-native formulation: a compressor is a pure function around
``lax.pmean``/``psum`` inside a ``shard_map`` over the ``data`` axis.  Any
per-worker persistent state (error-feedback residuals, PowerSGD factors) is
carried explicitly as a *sync state* pytree, sharded so each data shard owns
its own slice — functional replacement for the reference's stateful mirror
variables.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from autodist_tpu.kernel.synchronization import quant_ring


class Compressor:
    """Base: compress → all-reduce → decompress, with optional state.

    ``bucketable`` marks compressors whose wire format composes with the
    FLAT gradient buckets of the explicit path (``bucketing.py``): the
    compression must be elementwise (or flat-vector) so quantizing one
    concatenated bucket equals quantizing its members — the EQuARX
    per-collective scale grid.  Bucketable compressors also implement
    :meth:`reduce_scatter`, the ZeRO-1 leg: reduce the bucket but return
    only this shard's ``1/axis_size`` slice of the mean, so the weight
    update can run on the local optimizer-state shard.

    Quantized-wire compressors (int8/fp8, ``quant_ring.WIRE_FORMATS``)
    additionally implement the bucket-level :meth:`bucket_reduce` /
    :meth:`bucket_reduce_scatter` entry points the explicit path lowers
    through: they take the schedule IR's resolved algorithm (per-hop
    requantizing ring vs one-shot collective) and return the
    post-quantization saturation count alongside the reduced value and
    the new error-feedback state.
    """

    name = "Compressor"
    bucketable = True

    def init_state(self, var_value) -> Any:
        """Per-device sync state for one variable or bucket (local shape —
        the explicit path stacks it along a leading per-shard axis).
        None if stateless."""
        return None

    def reduce(self, grad, state, axis_name: str) -> Tuple[Any, Any]:
        """Return (globally averaged gradient, new state)."""
        raise NotImplementedError

    def reduce_scatter(self, vec, state, axis_name: str) -> Tuple[Any, Any]:
        """Return (this shard's slice of the globally averaged ``vec``,
        new state).  ``vec`` is a flat bucket whose length divides the
        axis size (``bucketing`` pads the tail).  Only defined for
        ``bucketable`` compressors."""
        raise NotImplementedError(
            f"{self.name} does not support reduce-scatter (ZeRO-1) mode")


class NoneCompressor(Compressor):
    """Identity compression: plain pmean (reference compressor.py:36-96)."""

    name = "NoneCompressor"

    def reduce(self, grad, state, axis_name):
        return lax.pmean(grad, axis_name), state

    def reduce_scatter(self, vec, state, axis_name):
        n = lax.axis_size(axis_name)
        shard = lax.psum_scatter(vec, axis_name, scatter_dimension=0,
                                 tiled=True)
        return shard / n, state


class HorovodCompressor(Compressor):
    """Cast-down compression: reduce in lower precision, cast back
    (reference compressor.py:146-176).  On TPU the wire format is bfloat16 —
    same exponent range as fp32, so no overflow handling is needed."""

    name = "HorovodCompressor"

    def __init__(self, wire_dtype=jnp.bfloat16):
        self._wire = wire_dtype

    def reduce(self, grad, state, axis_name):
        orig = grad.dtype
        compressed = grad.astype(self._wire)
        summed = lax.pmean(compressed, axis_name)
        return summed.astype(orig), state

    def reduce_scatter(self, vec, state, axis_name):
        n = lax.axis_size(axis_name)
        shard = lax.psum_scatter(vec.astype(self._wire), axis_name,
                                 scatter_dimension=0, tiled=True)
        return (shard / n).astype(vec.dtype), state


class HorovodCompressorEF(Compressor):
    """Error-feedback cast compression (reference compressor.py:208-284):
    the quantization error of each round is added back before the next
    compression, preserving convergence (Karimireddy et al., 2019)."""

    name = "HorovodCompressorEF"

    def __init__(self, wire_dtype=jnp.bfloat16):
        self._wire = wire_dtype

    def init_state(self, var_value):
        return jnp.zeros_like(var_value)

    def reduce(self, grad, state, axis_name):
        corrected = grad + state
        compressed = corrected.astype(self._wire)
        new_state = corrected - compressed.astype(grad.dtype)  # local residual
        summed = lax.pmean(compressed, axis_name)
        return summed.astype(grad.dtype), new_state

    def reduce_scatter(self, vec, state, axis_name):
        # Residual is computable locally BEFORE the scatter (it depends
        # only on this device's quantization error), so error feedback
        # composes with the ZeRO-1 leg at full-bucket state size.
        n = lax.axis_size(axis_name)
        corrected = vec + state
        compressed = corrected.astype(self._wire)
        new_state = corrected - compressed.astype(vec.dtype)
        shard = lax.psum_scatter(compressed, axis_name,
                                 scatter_dimension=0, tiled=True)
        return (shard / n).astype(vec.dtype), new_state


class PowerSGDCompressor(Compressor):
    """Rank-r PowerSGD (Vogels et al., 2019).  The reference carries a
    commented-out implementation (compressor.py:208-284 vicinity); on TPU the
    two small matmuls ride the MXU so low-rank compression is near-free.

    Only applied to rank-2 gradients; others fall back to pmean.  State is
    ``(Q, residual)``: the power-iteration basis and the error feedback.
    """

    name = "PowerSGDCompressor"
    # Low-rank factors need the 2-D gradient; flattening into a bucket
    # would silently disable the compression (every flat vector falls
    # back to pmean), so PowerSGD vars keep their per-variable collective.
    bucketable = False

    def __init__(self, rank: int = 1):
        self.rank = rank

    def init_state(self, var_value):
        shape = tuple(var_value.shape)
        if len(shape) != 2:
            return None
        n, m = shape
        # Deterministic init: varied, full-rank-ish basis.
        q = jax.random.normal(jax.random.PRNGKey(n * 31 + m), (m, self.rank),
                              dtype=var_value.dtype)
        residual = jnp.zeros(shape, var_value.dtype)
        return {"q": q, "residual": residual}

    def reduce(self, grad, state, axis_name):
        if state is None or grad.ndim != 2:
            return lax.pmean(grad, axis_name), state
        q, residual = state["q"], state["residual"]
        corrected = grad + residual
        # P = M Q ; all-reduce P ; orthonormalize ; Q = Mᵀ P̂ ; all-reduce Q
        p = corrected @ q
        p = lax.pmean(p, axis_name)
        p_hat, _ = jnp.linalg.qr(p)
        new_q = corrected.T @ p_hat
        new_q = lax.pmean(new_q, axis_name)
        approx = p_hat @ new_q.T
        new_residual = corrected - approx
        return approx, {"q": new_q, "residual": new_residual}


class QuantizedRingCompressor(Compressor):
    """Quantized-wire all-reduce with error feedback on the per-chunk
    scale grid (EQuARX-style, arxiv 2506.17615: quantized collectives
    cut ICI/DCN bytes ~4x vs f32 at negligible quality loss when
    error-compensated).

    The collectives are built MANUALLY so the 1-byte wire format is what
    actually crosses the interconnect (a dtype round-trip in front of
    ``psum`` would still move 4 bytes/element).  ALL tiers share one
    quantization rule — ``quant_ring.quantize_blocks``'s per-chunk
    scale grid, scales traveling with the payload: the single-collective
    ``all_to_all`` reduce-scatter + re-quantized ``all_gather`` used
    here and by the GSPMD/per-variable tier, and the per-hop
    requantizing ppermute ring the explicit bucketed path lowers to via
    :meth:`bucket_reduce` when the schedule IR resolves ``alg="ring"``.
    Stage-1 quantization error is carried as local error-feedback state
    (Karimireddy et al., 2019); stage-2 (post-aggregation) error is
    uncompensated, as in EQuARX.  Subclasses pin the wire format
    (int8 or fp8 e4m3 via ml_dtypes).
    """

    name = "QuantizedRingCompressor"
    wire = quant_ring.WIRE_INT8

    def init_state(self, var_value):
        return jnp.zeros_like(var_value)

    def reduce(self, grad, state, axis_name):
        n = lax.axis_size(axis_name)
        flat = (grad + state).astype(jnp.float32).ravel()
        pad = (-flat.size) % n
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
        mean, new_state, _ = quant_ring.quant_bucket_reduce(
            flat, jnp.zeros_like(flat), axis_name, n, self.wire,
            mode="all_reduce", alg="fused")
        new_state = new_state[:grad.size].reshape(grad.shape) \
            .astype(grad.dtype)
        return mean[:grad.size].reshape(grad.shape).astype(grad.dtype), \
            new_state

    def reduce_scatter(self, vec, state, axis_name):
        # ZeRO-1 leg = EQuARX stage 1 alone: the quantized reduce-scatter
        # already puts 1-byte payloads on the wire; the stage-2
        # re-quantized all-gather is simply not needed (fresh params are
        # gathered instead).  No stage-2 quantization error either.
        n = lax.axis_size(axis_name)
        shard, new_state, _ = self.bucket_reduce_scatter(
            vec, state, axis_name, n, alg="fused")
        return shard, new_state

    # -- bucket-level entry points (explicit path; docs/overlap.md) -------
    def bucket_reduce(self, vec, state, axis_name, n, alg="fused",
                      hop_fused=False):
        """Full mean of flat ``vec`` through the quantized wire under
        the IR-resolved ``alg``; returns ``(mean, new_state,
        sat_count)`` — the saturation counter feeds GradHealth.
        ``hop_fused`` selects the fused Pallas hop boundary for ring
        chains (the IR bucket node's ``hop_fused`` flag,
        docs/kernels.md)."""
        return quant_ring.quant_bucket_reduce(
            vec, state, axis_name, n, self.wire,
            mode="all_reduce", alg=alg, fused=hop_fused)

    def bucket_reduce_scatter(self, vec, state, axis_name, n, alg="fused",
                              hop_fused=False):
        """This device's 1/n mean shard (ZeRO-1 leg) — the update runs
        on the f32-dequantized shard; returns ``(shard, new_state,
        sat_count)``."""
        return quant_ring.quant_bucket_reduce(
            vec, state, axis_name, n, self.wire,
            mode="reduce_scatter", alg=alg, fused=hop_fused)


class Int8Compressor(QuantizedRingCompressor):
    """Int8 wire (±127 grid), per-chunk scales."""

    name = "Int8Compressor"
    wire = quant_ring.WIRE_INT8


class Fp8Compressor(QuantizedRingCompressor):
    """Fp8 e4m3 wire (``ml_dtypes.float8_e4m3fn``, max finite 448):
    same byte count as int8 with a floating grid — more dynamic range
    per block, coarser steps near the block amax."""

    name = "Fp8Compressor"
    wire = quant_ring.WIRE_FP8_E4M3


_REGISTRY: Dict[str, type] = {
    c.name: c for c in (NoneCompressor, HorovodCompressor, HorovodCompressorEF,
                        PowerSGDCompressor, Int8Compressor, Fp8Compressor)
}


def get_compressor(name: str) -> Compressor:
    if name not in _REGISTRY:
        raise ValueError(f"unknown compressor {name!r}; "
                         f"available: {sorted(_REGISTRY)}")
    return _REGISTRY[name]()
