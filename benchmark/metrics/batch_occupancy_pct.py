"""Output tokens the engine generated over the slot-ticks it paid for:
generated / (ticks * slots), both from ``/v1/stats`` over the window."""


def read(run):
    c = run.counters
    if "stats1" not in c:
        return None
    ticks = c["stats1"]["ticks"] - c["stats0"]["ticks"]
    gen = c["stats1"]["generated_tokens"] - c["stats0"]["generated_tokens"]
    if ticks <= 0:
        return None
    return 100.0 * gen / (ticks * c["engine"]["slots"])
