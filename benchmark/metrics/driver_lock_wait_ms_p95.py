"""95th percentile of the driver thread's wait for the server lock
before a tick (``server/lock_wait``), in ms: how long the open streams'
polls and the submitting handlers hold the engine back.  From the span
ring (as much of the window as its 4096 records still hold)."""
from benchmark import program_spans
from benchmark.harness import percentile


def read(run):
    waits = [1e3 * (s["end"] - s["start"]) for s in
             program_spans.ring_spans("server/lock_wait",
                                      since=run.window_start)]
    return percentile(waits, 95)
