"""Seconds of the session's first step (``session/run`` with ``step`` 0):
tracing, lowering, compiling or fetching the step program, and running
it once; from the span ring on the host clock."""
from benchmark import program_spans


def read(run):
    first = [s for s in program_spans.ring_spans("session/run")
             if s["ids"].get("step") == 0]
    if not first:
        return None
    return first[0]["end"] - first[0]["start"]
