"""Peak bytes in use on the fullest chip, in GB."""


def read(run):
    peak = run.counters.get("memory_peak_bytes")
    return None if peak is None else peak / 1e9
