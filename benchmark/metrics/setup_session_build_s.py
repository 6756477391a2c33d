"""Seconds of ``create_distributed_session``: the sum of the program's
``setup/*`` spans (strategy, its compilation, pre-flight, transform,
placing the parameters, the optimizer's and the synchronizer's state,
the cost estimate), from the span ring on the host clock."""
from benchmark import program_spans


def read(run):
    spans = [s for s in program_spans.ring()
             if s["name"].startswith("setup/")]
    if not spans:
        return None
    return sum(s["end"] - s["start"] for s in spans)
