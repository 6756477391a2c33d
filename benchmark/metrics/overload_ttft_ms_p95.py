"""Time to first token at the client above capacity: recorded, not
judged (the queue grows all through the window)."""
from benchmark.harness import percentile


def read(run):
    return percentile(run.counters.get("client", {}).get("ttft_ms", []), 95)
