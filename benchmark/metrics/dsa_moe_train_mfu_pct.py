"""Model FLOP/s utilization of a ``KeyeVL2`` share: FLOPs a trained token
needs from the configuration's keys (``benchmark/flops_dsa_moe.py``: main
attention over the SELECTED pairs only, the indexer forward only, the
experts at the EXPECTED share of a token's picks that this chip holds, no
recomputation), times tokens per second per chip, over the chip's peak.
The seconds are the steps' own, as in ``train_mfu_pct``."""
from benchmark import flops_dsa_moe as flops


def read(run):
    c, cfg = run.counters, run.cell.config
    if "steps" not in c or run.peaks is None or "sa_config" not in cfg:
        return None
    per_token = flops.train_flops_per_token(cfg, c["seq_len"])
    rate = c["steps"] * c["tokens_per_step"] / sum(c["step_s"]) \
        / len(run.devices)
    return 100.0 * per_token * rate / run.peaks["flops_per_s_bf16"]
