"""Model FLOP/s utilization of an LFM2-MoE share: FLOPs a trained token
needs from the configuration's keys (``benchmark/flops_sconv_moe.py``: the
mixers' products and taps, both gates, attention over the causal pairs,
the dense FFN, the experts at the EXPECTED share of a token's picks that
this chip holds, the head; no recomputation), times tokens per second per
chip, over the chip's peak: the share of the WHOLE step.  The seconds are
the steps' own, as in ``train_mfu_pct``."""
from benchmark import flops_sconv_moe as flops


def read(run):
    c, cfg = run.counters, run.cell.config
    if ("steps" not in c or run.peaks is None
            or "conv_L_cache" not in cfg):
        return None
    per_token = flops.train_flops_per_token(cfg, c["seq_len"])
    rate = c["steps"] * c["tokens_per_step"] / sum(c["step_s"]) \
        / len(run.devices)
    return 100.0 * per_token * rate / run.peaks["flops_per_s_bf16"]
