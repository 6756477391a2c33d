"""Throughput and MFU instrumentation.

The reference measured throughput only in example scripts (TimeHistory,
``examples/benchmark/imagenet.py:85-120``); here it is a framework feature:
:class:`ThroughputMeter` is fed by every ``DistributedSession.run`` call,
and :func:`session_mfu` turns XLA's compiled cost analysis into a
model-FLOPs-utilization figure against the chip's peak — the metric TPU
work is judged by (``sess.throughput()`` / ``sess.mfu()``; the benchmark
counts FLOPs from the configuration instead: ``benchmark/flops.py``).
"""
from __future__ import annotations

import time
from collections import deque
from typing import Any, Dict, Optional

# Peak dense bf16 FLOP/s per chip, keyed by the exact PJRT ``device_kind``.
# Each entry names its source; a kind is added when a run has shown the
# string PJRT reports for it.
PEAK_FLOPS_BY_KIND = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip.
    "TPU v5 lite": 197e12,
}


def peak_flops_per_chip(device) -> Optional[float]:
    """Peak dense bf16 FLOP/s of ``device``: None for a non-TPU device
    (no utilization is defined there); a TPU whose ``device_kind`` is not
    in :data:`PEAK_FLOPS_BY_KIND` raises, because a guessed peak would
    turn into a wrong utilization under the right name."""
    if device.platform != "tpu":
        return None
    try:
        return PEAK_FLOPS_BY_KIND[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s on record for TPU device_kind "
            f"{device.device_kind!r}; add it to PEAK_FLOPS_BY_KIND with "
            f"its source") from None


class ThroughputMeter:
    """Sliding-window step-time tracker (last ``window`` steps).

    Wall-clock between consecutive ``tick()`` calls — with async dispatch
    (``sess.run(sync=False)``) this measures the DISPATCH rate until the
    pipeline fills, then converges to true step time; synchronous runs
    measure it directly."""

    def __init__(self, window: int = 50):
        self._times: deque = deque(maxlen=window + 1)

    def tick(self) -> None:
        self._times.append(time.perf_counter())

    @property
    def steps_recorded(self) -> int:
        return max(0, len(self._times) - 1)

    def step_time(self) -> Optional[float]:
        """Mean seconds/step over the window (None until 2 ticks)."""
        if len(self._times) < 2:
            return None
        return (self._times[-1] - self._times[0]) / (len(self._times) - 1)

    def stats(self, items_per_step: Optional[int] = None) -> Dict[str, Any]:
        st = self.step_time()
        out: Dict[str, Any] = {
            "steps_measured": self.steps_recorded,
            "step_time_ms": None if st is None else round(st * 1e3, 3),
            "steps_per_sec": None if st in (None, 0.0) else round(1.0 / st, 3),
        }
        if items_per_step is not None and st not in (None, 0.0):
            out["items_per_sec"] = round(items_per_step / st, 2)
        return out


def step_flops(step_fn, *args) -> Optional[float]:
    """Model FLOPs of one compiled step from XLA's cost analysis (exact for
    the program that runs); None when the backend doesn't expose it.

    Note: ``lower().compile()`` is AOT — on a cold jit cache this compiles
    the program a second time, so call it once and cache the result."""
    try:
        cost = step_fn.lower(*args).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = float(cost.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception:  # pragma: no cover - backend-dependent
        return None


def mfu(flops_per_step: float, step_time_s: float, devices) -> Optional[float]:
    """Model FLOPs utilization: per-step model FLOPs over what the mesh's
    chips could do in that wall time (None off-TPU)."""
    peaks = [peak_flops_per_chip(d) for d in devices]
    if not step_time_s or None in peaks:
        return None
    return flops_per_step / step_time_s / sum(peaks)
