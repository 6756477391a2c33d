"""Pairs of query and key whose score the attention kernels form over the
pairs the model attends to (a window layer's window, a global layer's
causal triangle): the price of whole tiles at the diagonal and at the
window's trailing edge; a kernel that did not skip the tiles behind the
window would read 1.78 at 16,384 tokens and a window of 4,096.  Both from
the gauges the program sets when it traces the model
(``autodist_swa_pairs_per_step{kind="computed"|"attended"}``); None where
the program set none."""


def read(run):
    try:
        from autodist_tpu.telemetry.registry import DEFAULT_REGISTRY
    except ImportError:
        return None
    pairs = {m.labels.get("kind"): m.value
             for m in DEFAULT_REGISTRY.metrics()
             if m.name == "autodist_swa_pairs_per_step"}
    if not pairs.get("computed") or not pairs.get("attended"):
        return None
    return pairs["computed"] / pairs["attended"]
