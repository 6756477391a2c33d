"""Seconds jax spent TRACING and LOWERING before the window (the
``compile/trace`` and ``compile/lower`` records the program's
``jax.monitoring`` listener writes; XLA's own time is ``compile_s``).
The union of their intervals, because jax times a nested trace inside
its caller's too; those inside the benchmark's ``bench/reference`` and
``bench/lowered_types`` records are left out, as ``setup_s`` leaves
their seconds out."""
from benchmark import program_spans

NOT_SETUP = ("bench/reference", "bench/lowered_types")


def read(run):
    spans = [s for name in ("compile/trace", "compile/lower")
             for s in program_spans.ring_spans(name,
                                               before=run.window_start)]
    if not spans:
        return None
    return program_spans.union_seconds(
        spans, [(a, b) for name, a, b in run.spans.records
                if name in NOT_SETUP])
