"""Share of the peak HBM bandwidth that decoding reaches: the bytes one
decode tick must read (every layer matrix, the tied table, the K/V of the
tokens live in the window on average; from shapes) over the device time
of one tick of the decode program in the trace, against the chip's peak
bytes/s."""
from benchmark import flops

DECODE = r"paged_chunk"


def read(run):
    red, c = run.trace_reduction, run.counters
    if red is None or run.peaks is None or "engine" not in c:
        return None
    runs, seconds = red.module_runs(DECODE)
    if not runs:
        return None
    ticks = c["stats1"]["ticks"] - c["stats0"]["ticks"]
    chunks = c["stats1"]["chunks"] - c["stats0"]["chunks"]
    if chunks <= 0:
        return None
    per_tick_s = seconds / (runs * ticks / chunks)
    need = flops.decode_tick_bytes(
        run.cell.config, layer_weight_bytes=4, table_bytes=2, kv_bytes=2,
        live_tokens=c["mean_live_tokens"])
    return 100.0 * need / per_tick_s / run.peaks["hbm_bytes_per_s"]
