"""The global layers' attention kernels' share of the roofline of the
causal triangle's pairs.

As ``window_attention_roofline`` (its ``share``, loaded from its file),
for the layers that attend to every earlier key: the least time from
``benchmark/flops_swa_moe.py: global_attention_call`` times the global
layers and the step programs the trace holds whole, over the device time
of the ``global_attn`` Pallas calls (the full causal flash kernel under
the program's scope ``swa/attention``).  None without a trace, or where
the trace holds no such call."""
import importlib.util
import os

from benchmark import flops_swa_moe as flops


def _window_reader():
    spec = importlib.util.spec_from_file_location(
        "window_attention_roofline", os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "window_attention_roofline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def global_layers(cfg):
    return len(cfg["sliding_window_layout"]) - flops.window_layers(cfg)


def read(run):
    return _window_reader().share(
        run, "global_attn", flops.global_attention_call, global_layers,
        "global")
