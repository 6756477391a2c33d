"""Seconds of XLA compilation (or fetching from the persistent cache)
before the window, from jax's monitoring events."""


def read(run):
    return run.counters["setup_compile"]["compile_s"]
