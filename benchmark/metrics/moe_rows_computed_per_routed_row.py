"""Rows the grouped expert products are handed a step over the rows an
even router sends here: the price of static shapes with no token
dropped.  Both from the gauges the program sets when it traces the model
(``autodist_moe_rows_per_step{kind="computed"|"expected"}``); None where
the program set none."""


def read(run):
    try:
        from autodist_tpu.telemetry.registry import DEFAULT_REGISTRY
    except ImportError:
        return None
    rows = {m.labels.get("kind"): m.value
            for m in DEFAULT_REGISTRY.metrics()
            if m.name == "autodist_moe_rows_per_step"}
    if not rows.get("computed") or not rows.get("expected"):
        return None
    return rows["computed"] / rows["expected"]
