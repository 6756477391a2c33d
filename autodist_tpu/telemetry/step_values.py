"""Small values a model computes INSIDE a training step, brought to the
host with the step's loss.

A model that wants a per-step number on the host (how many rows its expert
layers took, say) has two ways out of a jitted step.  A host callback
(``jax.debug.callback``) is one line, and it costs the step program its
place in JAX's persistent compilation cache: an executable with a host
callback in it is never written there, so every process would compile the
step anew.  This is the other way: the value leaves with the step's
metrics, which the session fetches anyway.

* The model marks its loss function (``reporting(loss_fn)``) and, while
  that function is traced, calls ``emit(name, value, publish)`` at its TOP
  level (not under a ``jax.checkpoint``, a ``scan`` or a ``jit`` of its
  own: the value has to be one the function could return).
* ``GraphTransformer`` (the GSPMD path) traces a marked loss function under
  a :class:`Collector`, which hands the emitted values back beside the
  loss; they ride in the step's metrics under ``KEY``.
* ``DistributedSession.run(sync=True)`` takes them out of the fetched
  metrics and calls each value's ``publish(host_array)``.  With gradient
  accumulation a value arrives stacked over the microbatches.

Anywhere else (the function called or differentiated directly, the
explicit sync path, ``evaluate``, ``run(sync=False)``) ``emit`` does
nothing and nothing is published.
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Optional

#: the key of the emitted values in a step's metrics, before ``run`` takes
#: them out
KEY = "step_values"

_tracing = threading.local()


def reporting(loss_fn: Callable) -> Callable:
    """Mark ``loss_fn`` as one that emits step values."""
    loss_fn.reports_step_values = True
    return loss_fn


def emit(name: str, value: Any, publish: Callable[[Any], None]) -> None:
    """Hand ``value`` (a traced array or a pytree of them) to the collector
    the calling loss function is traced under, if any; ``publish`` gets it
    as host numpy after every step that ran."""
    collecting = getattr(_tracing, "collecting", None)
    if collecting is not None:
        collector, values = collecting
        values[name] = value
        collector.publishers[name] = publish


class Collector:
    """The publishers of what one step program's loss function emits (the
    values themselves are a trace's and leave with its result)."""

    def __init__(self):
        self.publishers: Dict[str, Callable[[Any], None]] = {}

    @staticmethod
    def wanted_by(loss_fn: Callable) -> Optional["Collector"]:
        return Collector() if getattr(
            loss_fn, "reports_step_values", False) else None

    def wrap(self, loss_fn: Callable, has_aux: bool) -> Callable:
        """``loss_fn`` returning ``(loss, (aux or None, emitted values))``
        (for ``jax.value_and_grad(..., has_aux=True)``)."""
        def collected(*args):
            values: Dict[str, Any] = {}
            _tracing.collecting = self, values
            try:
                out = loss_fn(*args)
            finally:
                _tracing.collecting = None
            loss, aux = out if has_aux else (out, None)
            return loss, (aux, values)

        return collected

    def publish(self, values: Optional[Dict[str, Any]]) -> None:
        for name, value in (values or {}).items():
            self.publishers[name](value)
