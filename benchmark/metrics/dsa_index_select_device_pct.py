"""Share of a step's device time in choosing the keys: the indexer's
projections and scores (the program's scope ``dsa/index``) and the top-k
of every row with its packing (``dsa/select``), by each operation's
``tf_op`` as ``moe_routed_device_pct`` reads it (its wire-format reader
and its sum over whole step programs, loaded from its file).  None
without a trace, or where no operation of a step names such a scope."""
import importlib.util
import os
import re

SCOPES = re.compile(r"\bdsa/(index|select)\b")
NO_KERNEL = re.compile(r"(?!)")     # these scopes hold no call XLA renames


def _routed_reader():
    spec = importlib.util.spec_from_file_location(
        "moe_routed_device_pct", os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "moe_routed_device_pct.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(run):
    red = run.trace_reduction
    path = run.tracer.xplane_path() if red is not None else None
    if path is None or not red.chips:
        return None
    routed = _routed_reader()
    total, by = routed.scoped_seconds(
        red, routed.operation_strings(path), scope=SCOPES, kernels=NO_KERNEL)
    if not total or not by:
        return None
    print("dsa device time by scope (s of the traced steps): "
          + ", ".join(f"{k} {v:.4f}" for k, v in sorted(by.items()))
          + f"; all operations {total:.4f}", flush=True)
    return 100.0 * sum(by.values()) / total
