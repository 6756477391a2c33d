"""Share of the traced window in which no operation ran on the device
(1 - union of op intervals / window, averaged over the chips)."""


def read(run):
    red = run.trace_reduction
    if red is None or not red.window_s or not red.busy_s:
        return None
    return 100.0 * red.idle_share
