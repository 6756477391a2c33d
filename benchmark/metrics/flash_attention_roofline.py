"""The flash-attention kernels' share of their roofline.

Numerator: the least time the chip could take for one layer's attention
of one step, forward and backward: the larger of FLOPs / peak FLOP/s and
bytes / peak bytes/s, both from shapes (``benchmark/flops.py``), times the
layers and the step programs the trace holds whole.  Denominator: the
device time of the Pallas calls inside those step programs: ``%attn...``
on one chip and ``%shard_map...`` on a mesh (XLA names the call by the
innermost scope, and ``ops/flash_attention.py`` wraps it in a
``shard_map`` there), ``custom_call_target="tpu_custom_call"``, whose
first result is ONE CHIP'S ``f32[rows, heads, T, D]``.  Both sides are one
chip's: the trace's first chip, a chip's rows.  The kernels read and write
float32 here (flax promotes the activations against float32 weights), so
the bytes are counted at 4 a number; which bound is the larger is
printed."""
from benchmark import flops

STEP = r"^jit_step\b"
#: by name and by the shape of the first result (rows, heads, T, D)
KERNEL = (r'^%?(?:attn|shard_map)[\w.\-]* = \(?f32\[{},{},{},{}\]'
          r'.*custom_call_target="tpu_custom_call"')


def read(run):
    red, c = run.trace_reduction, run.counters
    if red is None or run.peaks is None or "global_batch" not in c:
        return None
    cfg = run.cell.config
    heads, hd = cfg["n_head"], cfg["n_embd"] // cfg["n_head"]
    rows = c["global_batch"] // len(run.devices)
    steps, calls, seconds = red.ops_in_module_runs(
        STEP, KERNEL.format(rows, heads, c["seq_len"], hd))
    if not steps or not calls or not seconds:
        return None
    least, bound = 0.0, []
    for backward in (False, True):
        f, b = flops.flash_call(rows, heads, c["seq_len"], hd, 4,
                                backward=backward)
        t_f = f / run.peaks["flops_per_s_bf16"]
        t_b = b / run.peaks["hbm_bytes_per_s"]
        least += max(t_f, t_b)
        bound.append("flops" if t_f >= t_b else "bytes")
    print(f"flash roofline: {calls} calls in {steps} steps, "
          f"{seconds / steps * 1e3:.3f} ms a step, least "
          f"{least * cfg['n_layer'] * 1e3:.3f} ms, bound by "
          f"{bound[0]} forward and {bound[1]} backward", flush=True)
    return 100.0 * least * cfg["n_layer"] * steps / seconds
