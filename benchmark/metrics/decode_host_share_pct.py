"""The host's share of the engine's ticks in the window: 100 x (time in
``engine/step`` - time in ``engine/host_sync``) / time in ``engine/step``.
``engine/host_sync`` is where the driver thread waits for the device
(``np.array(done)`` after a decode chunk, the landed tokens of a prefill,
the predictions of a speculative round); the rest of a step is Python:
harvest, admission, building arguments, dispatch.  From the span ring
(as much of the window as its 4096 records still hold)."""
from benchmark import program_spans


def read(run):
    steps = program_spans.ring_spans("engine/step", since=run.window_start)
    total = sum(s["end"] - s["start"] for s in steps)
    if not total:
        return None
    waits = sum(s["end"] - s["start"] for s in program_spans.ring_spans(
        "engine/host_sync", since=run.window_start))
    return 100.0 * (total - waits) / total
