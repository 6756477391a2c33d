"""Pairs of query and key whose score the attention kernels form over the
pairs the indexer selected: the price of masking inside tiles that are
computed whole.  Both from the gauges the program sets when it traces the
model (``autodist_dsa_pairs_per_step{kind="computed"|"selected"}``); None
where the program set none."""


def read(run):
    try:
        from autodist_tpu.telemetry.registry import DEFAULT_REGISTRY
    except ImportError:
        return None
    pairs = {m.labels.get("kind"): m.value
             for m in DEFAULT_REGISTRY.metrics()
             if m.name == "autodist_dsa_pairs_per_step"}
    if not pairs.get("computed") or not pairs.get("selected"):
        return None
    return pairs["computed"] / pairs["selected"]
