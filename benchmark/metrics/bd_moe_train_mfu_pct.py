"""Model FLOP/s utilization of an ``sdar_moe`` share trained by diffusion
over blocks: FLOPs a trained DATA token needs from the configuration's
keys (``benchmark/flops_bd_moe.py``: its two rows through the layers, its
noised row through the head, attention over the mask's ATTENDED pairs, the
experts at the EXPECTED share of a row's picks that this chip holds, no
recomputation), times data tokens per second per chip, over the chip's
peak: the share of the WHOLE step.  The seconds are the steps' own, as in
``train_mfu_pct``."""
from benchmark import flops_bd_moe as flops


def read(run):
    c, cfg = run.counters, run.cell.config
    if ("steps" not in c or run.peaks is None
            or "block_diffusion" not in cfg):
        return None
    per_token = flops.train_flops_per_token(cfg, c["seq_len"])
    rate = c["steps"] * c["tokens_per_step"] / sum(c["step_s"]) \
        / len(run.devices)
    return 100.0 * per_token * rate / run.peaks["flops_per_s_bf16"]
