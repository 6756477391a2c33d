"""Share of the traced window in which chip 0 ran nothing while the
session was fetching the step's metrics (``autodist/session/fetch``: from
the device's last operation until the loss is on the host), by exact
overlap."""
from benchmark import program_spans


def read(run):
    return program_spans.idle_pct(run, ("session/fetch",))
