"""The window layers' attention kernels' share of the roofline of the
WINDOW's pairs.

The least time the chip could take for one window layer's attention of
one step over the pairs inside the window, forward and backward: the
larger of FLOPs / peak FLOP/s and bytes / peak bytes/s from shapes
(``benchmark/flops_swa_moe.py: window_attention_call``; float32 in and
out), times the window layers and the step programs the trace holds
whole, over the device time of the ``window_attn`` Pallas calls (the
program's scope ``swa/attention``) inside those programs.  The work is
the model's, whatever implements it: a kernel that computed every causal
tile would read under half of what one that skips the tiles behind the
window does.  The global layers' calls (``global_attn``) are
``global_attention_roofline``'s.  None without a trace, or where the
trace holds no such call (a program without the layer)."""
from benchmark import flops_swa_moe as flops

STEP = r"^jit_step\b"
KERNEL = r'^%?{}[\w.\-]* = .*custom_call_target="tpu_custom_call"'


def share(run, kernel, call, layers_of, kind):
    """The share for the layers of one ``kind``: the Pallas calls named
    ``kernel`` against ``call``'s FLOPs and bytes of one layer times
    ``layers_of(config)``."""
    red, c, cfg = run.trace_reduction, run.counters, run.cell.config
    if (red is None or run.peaks is None or "global_batch" not in c
            or "sliding_window_layout" not in cfg):
        return None
    steps, calls, seconds = red.ops_in_module_runs(STEP,
                                                   KERNEL.format(kernel))
    if not steps or not calls or not seconds:
        return None
    rows = c["global_batch"] // len(run.devices)
    least, bound = 0.0, []
    for backward in (False, True):
        f, b = call(rows, cfg, c["seq_len"], 4, backward=backward)
        t_f = f / run.peaks["flops_per_s_bf16"]
        t_b = b / run.peaks["hbm_bytes_per_s"]
        least += max(t_f, t_b)
        bound.append("flops" if t_f >= t_b else "bytes")
    layers = layers_of(cfg)
    print(f"{kind} attention roofline: {calls} calls in {steps} steps "
          f"({calls / steps / layers:g} a {kind} layer), "
          f"{seconds / steps * 1e3:.3f} ms a step, least "
          f"{least * layers * 1e3:.3f} ms, bound by {bound[0]} forward "
          f"and {bound[1]} backward", flush=True)
    return 100.0 * least * layers * steps / seconds


def read(run):
    return share(run, "window_attn", flops.window_attention_call,
                 flops.window_layers, "window")
