"""The recurrence's share of the roofline of the recurrence AS WRITTEN.

The least time the chip could take for the linear layers' gated delta
rule of one step, forward and backward: the larger of FLOPs / peak FLOP/s
and bytes / peak bytes/s from shapes (``benchmark/flops_gdn_moe.py:
gdn_scan_call``: 7 operations a state element a token forward, twice that
backward; float32 in and out), times the linear layers and the step
programs the trace holds whole, over the device time of EVERY operation
inside those programs whose ``tf_op`` names ``gdn_scan``: the scope
``ops/gated_delta_rule.py`` computes the whole chunked form under, so the
Pallas kernel of that name, the batched products that prepare its
operands and, in the backward, the bodies of the plain scans, each once
(a loop's own event is left out).  The work is the model's, whatever
implements it.  None without a trace, or where no operation of a step
names the scope (a program without the layer)."""
import re

from benchmark import flops_gdn_moe as flops
from benchmark.metrics import moe_routed_device_pct as routed

SCOPE = re.compile(r"\bgdn_scan\b")
NO_KERNEL = re.compile(r"(?!)")     # found by the scope, not by a name


def read(run):
    red, c, cfg = run.trace_reduction, run.counters, run.cell.config
    path = run.tracer.xplane_path() if red is not None else None
    if (path is None or not red.chips or run.peaks is None
            or "global_batch" not in c
            or "linear_num_value_heads" not in cfg):
        return None
    steps = sum(1 for name, _, _ in red.chips[0].modules
                if re.search(routed.STEP, name))
    _, by = routed.scoped_seconds(red, routed.operation_strings(path),
                                  scope=SCOPE, kernels=NO_KERNEL)
    seconds = sum(by.values())
    if not steps or not seconds:
        return None
    least, bound = flops.least_seconds(
        flops.gdn_scan_call, run.peaks,
        c["global_batch"] // len(run.devices), cfg, c["seq_len"], 4)
    layers = flops.linear_layers(cfg)
    print(f"gdn scan roofline: {steps} steps, {seconds / steps * 1e3:.3f} "
          f"ms a step under gdn_scan over {layers} linear layers, least "
          f"{least * layers * 1e3:.3f} ms, bound by {bound[0]} forward and "
          f"{bound[1]} backward", flush=True)
    return 100.0 * least * layers * steps / seconds
