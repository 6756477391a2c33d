"""Programs compiled (or fetched) inside the measured window: should be 0."""


def read(run):
    return run.counters["window_compile"]["compiles"]
