"""Share of the traced window in which chip 0 ran nothing and the
session was in none of place_batch, enqueue and fetch: under
``session/record``, between the parts of ``session/run``, or under no
``autodist/`` span at all (the caller's loop).  With the other three
``train_idle_*`` metrics it adds up to chip 0's idle share."""
from benchmark import program_spans

NAMED = ("session/place_batch", "session/enqueue", "session/fetch")


def read(run):
    by = program_spans.idle_split(run)
    if by is None:
        return None
    return program_spans.idle_pct(run, [n for n in by if n not in NAMED])
