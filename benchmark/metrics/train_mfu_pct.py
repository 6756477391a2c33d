"""Model FLOP/s utilization: FLOPs a token needs forward and backward,
from shapes (``benchmark/flops.py``, no recomputation), times tokens per
second per chip, over the chip's peak.  The seconds are those of the
steps themselves (their sum), so that the profiler's own start and stop,
which fall between steps of a traced run, do not count as training."""
from benchmark import flops


def read(run):
    c = run.counters
    if "steps" not in c or run.peaks is None:
        return None
    per_token = flops.train_flops_per_token(run.cell.config, c["seq_len"])
    rate = c["steps"] * c["tokens_per_step"] / sum(c["step_s"]) \
        / len(run.devices)
    return 100.0 * per_token * rate / run.peaks["flops_per_s_bf16"]
