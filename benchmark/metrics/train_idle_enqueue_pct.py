"""Share of the traced window in which chip 0 ran nothing while the
session was enqueueing the step (``autodist/session/enqueue``: from the
call of the jitted step to its return, so pytree flattening and launch,
until the device's first operation), by exact overlap."""
from benchmark import program_spans


def read(run):
    return program_spans.idle_pct(run, ("session/enqueue",))
