"""The attention kernels' share of the roofline of the pairs the
block-diffusion mask lets through.

The least time the chip could take for one layer's attention of one step
over a sequence and its noised copy, forward and backward: the larger of
FLOPs / peak FLOP/s and bytes / peak bytes/s from shapes
(``benchmark/flops_bd_moe.py: block_attention_call``: the MODEL's ``L (L +
B)`` pairs a head; float32 in and out), times the layers and the step
programs the trace holds whole, over the device time of the ``bd_attn``
Pallas calls (the program's scope ``bd/attention``) inside those programs.
The work is the model's, whatever implements it: a kernel that computed
every causal tile of the ``2 L`` rows and masked would read near half of
what one that skips by the rule does.  None without a trace, or where the
trace holds no such call."""
from benchmark import flops_bd_moe as flops

STEP = r"^jit_step\b"
KERNEL = r'^%?bd_attn[\w.\-]* = .*custom_call_target="tpu_custom_call"'


def read(run):
    red, c, cfg = run.trace_reduction, run.counters, run.cell.config
    if (red is None or run.peaks is None or "global_batch" not in c
            or "block_diffusion" not in cfg):
        return None
    steps, calls, seconds = red.ops_in_module_runs(STEP, KERNEL)
    if not steps or not calls or not seconds:
        return None
    rows = c["global_batch"] // len(run.devices)
    least, bound = 0.0, []
    for backward in (False, True):
        f, b = flops.block_attention_call(rows, cfg, c["seq_len"], 4,
                                          backward=backward)
        t_f = f / run.peaks["flops_per_s_bf16"]
        t_b = b / run.peaks["hbm_bytes_per_s"]
        least += max(t_f, t_b)
        bound.append("flops" if t_f >= t_b else "bytes")
    layers = cfg["num_hidden_layers"]
    print(f"block diffusion attention roofline: {calls} calls in {steps} "
          f"steps ({calls / steps / layers:g} a layer), "
          f"{seconds / steps * 1e3:.3f} ms a step, least "
          f"{least * layers * 1e3:.3f} ms, bound by {bound[0]} forward "
          f"and {bound[1]} backward", flush=True)
    return 100.0 * least * layers * steps / seconds
