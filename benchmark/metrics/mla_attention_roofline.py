"""The flash-attention kernels' share of their roofline where queries and
keys are wider than values (latent attention: Dk 192, Dv 128).

As ``flash_attention_roofline``: the least time the chip could take for
one layer's attention of one step, forward and backward, the larger of
FLOPs / peak FLOP/s and bytes / peak bytes/s from shapes
(``benchmark/flops_mla_moe.py``; float32 in and out, the causal half),
times the layers and the step programs the trace holds whole, over the
device time of the ``attn`` Pallas calls inside those programs.  A layer
that is rematerialised runs its forward call twice; the second is time
the kernel takes and work the roofline does not count.  Which bound is
the larger is printed."""
from benchmark import flops_mla_moe as flops

STEP = r"^jit_step\b"
KERNEL = r'^%?attn[\w.\-]* = .*custom_call_target="tpu_custom_call"'


def read(run):
    red, c, cfg = run.trace_reduction, run.counters, run.cell.config
    if (red is None or run.peaks is None or "global_batch" not in c
            or "qk_nope_head_dim" not in cfg):
        return None
    steps, calls, seconds = red.ops_in_module_runs(STEP, KERNEL)
    if not steps or not calls or not seconds:
        return None
    rows = c["global_batch"] // len(run.devices)
    least, bound = 0.0, []
    for backward in (False, True):
        f, b = flops.flash_call(
            rows, cfg["num_attention_heads"], c["seq_len"],
            flops.qk_width(cfg), cfg["v_head_dim"], 4, backward=backward)
        t_f = f / run.peaks["flops_per_s_bf16"]
        t_b = b / run.peaks["hbm_bytes_per_s"]
        least += max(t_f, t_b)
        bound.append("flops" if t_f >= t_b else "bytes")
    layers = cfg["num_hidden_layers"]
    print(f"mla attention roofline: {calls} calls in {steps} steps "
          f"({calls / steps / layers:g} a layer), "
          f"{seconds / steps * 1e3:.3f} ms a step, least "
          f"{least * layers * 1e3:.3f} ms, bound by {bound[0]} forward "
          f"and {bound[1]} backward", flush=True)
    return 100.0 * least * layers * steps / seconds
