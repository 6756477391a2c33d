"""The gated short convolution's share of the roofline of the mixer AS
WRITTEN.

The least time the chip could take for the conv layers' mixers of one
step, forward and backward: the larger of FLOPs / peak FLOP/s and bytes /
peak bytes/s from shapes (``benchmark/flops_sconv_moe.py:
short_conv_mixer_call``: ``W_in``, ``B * X``, three taps, ``C * c``,
``W_out``; float32 in and out), times the conv layers, over the device
time a step of EVERY operation under the program's scopes
``sconv/project`` and ``sconv/conv`` (``benchmark/step_scopes.py``'s
table: each operation once, a loop's own event left out), forward,
recomputed forward and backward, kernel or not.  The work is the model's,
whatever implements it, and it is taken over the WHOLE mixer: a gate that
XLA fuses into a product's epilogue moves time between the two scopes and
changes nothing here.  None without a trace, or where no operation of a
step is under either scope (a program without the layer)."""
from benchmark import flops_sconv_moe as flops
from benchmark import step_scopes

SCOPES = ("sconv/project", "sconv/conv")


def read(run):
    c, cfg = run.counters, run.cell.config
    if (run.peaks is None or "global_batch" not in c
            or "conv_L_cache" not in cfg):
        return None
    found = step_scopes.table(run)
    seconds = 0.0 if found is None else sum(
        found.by_scope.get(scope, 0.0) for scope in SCOPES)
    if not seconds:
        return None
    least, bound = flops.least_seconds(
        flops.short_conv_mixer_call, run.peaks,
        c["global_batch"] // len(run.devices), cfg, c["seq_len"], 4)
    layers = flops.conv_layers(cfg)
    print(f"short conv mixer roofline: {found.steps} steps, "
          f"{seconds / found.steps * 1e3:.3f} ms a step under sconv/* "
          f"over {layers} conv layers, least {least * layers * 1e3:.3f} "
          f"ms, bound by {bound[0]} forward and {bound[1]} backward",
          flush=True)
    return 100.0 * least * layers * found.steps / seconds
