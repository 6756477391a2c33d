"""How late the load generator sent: sent time - due time, p95."""
from benchmark.harness import percentile


def read(run):
    return percentile(run.counters.get("client", {}).get("late_ms", []), 95)
