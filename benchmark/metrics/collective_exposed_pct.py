"""Share of the traced window in which a chip ran a collective
(all-reduce, reduce-scatter, all-gather, ...) and nothing else."""


def read(run):
    red = run.trace_reduction
    if red is None or not red.window_s or len(red.chips) < 2:
        return None
    return 100.0 * red.exposed_seconds() / red.window_s
