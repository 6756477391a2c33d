"""The plain grouped-query attention kernels' share of the roofline of the
causal triangle's pairs at the layer's own head width.

As ``gated_attention_roofline``: the least time from
``benchmark/flops_sconv_moe.py: gqa_attention_call`` (float32 in and out)
times the attention layers and the step programs the trace holds whole,
over the device time of the ``gqa_attn`` Pallas calls (the causal flash
kernel under the program's scope ``gqa/attention``) inside those programs.
None without a trace, or where the trace holds no such call."""
from benchmark import flops_sconv_moe as flops

STEP = r"^jit_step\b"
KERNEL = r'^%?gqa_attn[\w.\-]* = .*custom_call_target="tpu_custom_call"'


def read(run):
    red, c, cfg = run.trace_reduction, run.counters, run.cell.config
    if (red is None or run.peaks is None or "global_batch" not in c
            or "conv_L_cache" not in cfg):
        return None
    steps, calls, seconds = red.ops_in_module_runs(STEP, KERNEL)
    if not steps or not calls or not seconds:
        return None
    least, bound = flops.least_seconds(
        flops.gqa_attention_call, run.peaks,
        c["global_batch"] // len(run.devices), cfg, c["seq_len"], 4)
    layers = flops.attention_layers(cfg)
    print(f"gqa attention roofline: {calls} calls in {steps} steps, "
          f"{seconds / steps * 1e3:.3f} ms a step, least "
          f"{least * layers * 1e3:.3f} ms, bound by {bound[0]} forward "
          f"and {bound[1]} backward", flush=True)
    return 100.0 * least * layers * steps / seconds
