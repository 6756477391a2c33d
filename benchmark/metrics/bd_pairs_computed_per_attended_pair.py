"""Pairs of query and key whose score the attention kernel forms over the
pairs the block-diffusion mask lets through (``L (L + B) / 2`` in each
half): the price of whole tiles at the rule's edge; a kernel that computed
every causal tile of the ``2 L`` rows would read 2.06 at 8,192 tokens,
blocks of 4 and tiles of 512, where skipping by the rule reads 1.125.
Both from the gauges the program sets when it traces the model
(``autodist_bd_pairs_per_step{kind="computed"|"attended"}``); None where
the program set none."""


def read(run):
    try:
        from autodist_tpu.telemetry.registry import DEFAULT_REGISTRY
    except ImportError:
        return None
    pairs = {m.labels.get("kind"): m.value
             for m in DEFAULT_REGISTRY.metrics()
             if m.name == "autodist_bd_pairs_per_step"}
    if not pairs.get("computed") or not pairs.get("attended"):
        return None
    return pairs["computed"] / pairs["attended"]
