"""Median host-clock time of one step: batch creation, ``sess.run`` and
``block_until_ready`` on the new parameters."""
from benchmark.harness import median


def read(run):
    steps = run.counters.get("step_s")
    return median(steps) * 1e3 if steps else None
