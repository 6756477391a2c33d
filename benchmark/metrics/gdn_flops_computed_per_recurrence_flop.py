"""FLOPs the chunked form of the gated delta rule performs at the model's
chunk over the FLOPs of the recurrence as written (7 a state element a
token): the price of turning 8,192 dependent steps into 128.  Both from
the gauges the program sets when it traces the model
(``autodist_gdn_flops_per_step{kind="computed"|"recurrence"}``); None
where the program set none."""


def read(run):
    try:
        from autodist_tpu.telemetry.registry import DEFAULT_REGISTRY
    except ImportError:
        return None
    flops = {m.labels.get("kind"): m.value
             for m in DEFAULT_REGISTRY.metrics()
             if m.name == "autodist_gdn_flops_per_step"}
    if not flops.get("computed") or not flops.get("recurrence"):
        return None
    return flops["computed"] / flops["recurrence"]
