"""Share of the steps' wall time that the session spent in its own
``dispatch`` phase (``StepRecord.phases["dispatch"]``: placing the batch
and enqueueing the step, before any wait for the device)."""


def read(run):
    d = [x for x in run.counters.get("dispatch_s", []) if x is not None]
    if not d or not run.counters.get("step_s"):
        return None
    return 100.0 * sum(d) / sum(run.counters["step_s"])
