"""Run one cell of the benchmark once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time; it holds the cell's chips alone.  It exits
non-zero, with no result line, unless ``jax.devices()`` are TPUs of a kind
``benchmark/peaks.json`` knows and at least as many as the cell asks for.
The cell's files are found by name (``BENCHMARK.json`` -> ``configs/``,
``workloads/``, ``traffic/``, ``entries/``, ``metrics/``); nothing here
names a cell.  The last line of standard output is the result.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse    # noqa: E402
import importlib   # noqa: E402
import os          # noqa: E402
import sys         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def place_caches():
    """JAX's persistent compilation cache at the program's fixed path
    (``<checkout>/.jax_cache``, or where JAX_COMPILATION_CACHE_DIR says),
    with no lower bound on what is worth caching: PR 21 saw sub-second
    programs recompiled in every warm run."""
    from autodist_tpu.utils.compile_cache import place_compile_cache

    path = place_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_process_start: float):
    """Everything after the chip check: drive the entry, reduce the
    trace, return the result line."""
    from benchmark import harness

    run = harness.Run(cell, seed, seconds, trace, devices, t_process_start)
    entry = importlib.import_module(
        "benchmark.entries." + cell.workload["entry"])
    keep = entry.run(run)        # noqa: F841  (state stays alive till here)
    run.counters.setdefault("memory_peak_bytes",
                            harness.memory_peak_bytes(devices))
    if trace:
        path = run.tracer.xplane_path()
        if path is not None:
            from benchmark import xplane

            run.trace_reduction = xplane.reduce(path, len(devices))
    return harness.result_line(run)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import harness

    cell = harness.Cell(args.workload)
    cache = place_caches()
    devices = harness.require_chip(cell)
    print(f"benchmark: {cell.name} seed={args.seed} "
          f"seconds={args.seconds:g} trace={args.trace} on "
          f"{len(devices)} x {devices[0].device_kind}; compile cache "
          f"{cache}", flush=True)
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                    devices, T_PROCESS_START)
    harness.emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
