"""Share of the traced window in which chip 0 ran nothing while the
session was placing the next batch (``autodist/session/place_batch``),
by exact overlap (``program_spans.idle_by_span``)."""
from benchmark import program_spans


def read(run):
    return program_spans.idle_pct(run, ("session/place_batch",))
