"""What every cell's run shares: finding the cell's files, the chip check,
compile counting, the benchmark's own spans, the profiler window, the
per-layer readers and the result line.

An ENTRY (``benchmark/entries/<entry>.py``, named by the workload file)
drives the system under test and fills a :class:`Run`; everything that
turns what it observed into numbers lives here, in ``xplane.py``,
``flops.py`` and ``metrics/``.
"""
from __future__ import annotations

import contextlib
import gc
import glob
import importlib.util
import json
import os
import shutil
import statistics
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# the cell: BENCHMARK.json's entry plus the files it names
# ---------------------------------------------------------------------------

class Cell:
    """One entry of ``workloads`` with its configuration, workload and
    traffic files, all found by name."""

    def __init__(self, name: str, root: str = ROOT, bench: dict = None):
        self.root = root
        self.bench = bench or load_json(root, "BENCHMARK.json")
        rows = [w for w in self.bench["workloads"] if w["name"] == name]
        if not rows:
            raise SystemExit(f"benchmark: no workload {name!r} in "
                             f"BENCHMARK.json")
        self.entry_row = rows[0]
        self.name = name
        self.chips = int(self.entry_row["chips"])
        cfg_row = next(c for c in self.bench["configs"]
                       if c["name"] == self.entry_row["config"])
        self.config = load_json(root, cfg_row["file"])
        base = os.path.dirname(os.path.dirname(
            os.path.join(root, cfg_row["file"])))
        self.dir = base
        self.workload = load_json(base, "workloads", name + ".json")
        self.traffic = load_json(base, "traffic",
                                 self.entry_row["traffic"] + ".json")
        self.peaks_table = load_json(HERE, "peaks.json")

    def metric_rows(self, group: str):
        """The metrics of ``end_to_end`` or ``per_layer`` that this cell
        reports: those with no ``workloads`` key whose ``moves`` (for a
        per-layer one) this cell reports, and those that list it."""
        mine = []
        e2e = {m["name"] for m in self.bench["end_to_end"]
               if self.name in m.get("workloads", [self.name])}
        for m in self.bench[group]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    mine.append(m)
            elif group == "end_to_end" or m["moves"] in e2e:
                mine.append(m)
        return mine


# ---------------------------------------------------------------------------
# the chip
# ---------------------------------------------------------------------------

def require_chip(cell: Cell):
    """The devices this cell runs on, or exit non-zero with no result."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"benchmark: needs a TPU, but jax.devices()[0] is "
            f"{dev.platform!r} ({dev.device_kind}); nothing was run")
    if dev.device_kind not in cell.peaks_table:
        raise SystemExit(
            f"benchmark: device kind {dev.device_kind!r} is not in "
            f"benchmark/peaks.json; nothing was run")
    if len(devices) < cell.chips:
        raise SystemExit(
            f"benchmark: {cell.name} needs {cell.chips} chips, "
            f"jax.devices() has {len(devices)}; nothing was run")
    return devices[:cell.chips]


def memory_peak_bytes(devices):
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


# ---------------------------------------------------------------------------
# compilation (copied from chip_smoke.py's CompileWatch, PR 21)
# ---------------------------------------------------------------------------

class CompileWatch:
    """Seconds spent in XLA compilation (or fetching from the persistent
    cache) and the cache's hits and misses, from jax's own monitoring
    events, which also see the server's driver thread.  ``mark()`` starts
    the measured window: compilations after it are counted apart."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        self._mark = None
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == self._COMPILE:
            with self._lock:
                self.compile_s += secs
                self.compiles += 1

    def _event(self, event, **_):
        with self._lock:
            self.hits += event == self._HIT
            self.misses += event == self._MISS

    def snapshot(self) -> dict:
        with self._lock:
            return {"compile_s": self.compile_s, "compiles": self.compiles,
                    "hits": self.hits, "misses": self.misses}

    def mark(self):
        self._mark = self.snapshot()

    def since_mark(self) -> dict:
        now = self.snapshot()
        return {k: now[k] - self._mark[k] for k in now}


# ---------------------------------------------------------------------------
# spans: the benchmark's own, around its calls into each layer
# ---------------------------------------------------------------------------

class Spans:
    """``with spans("bench/sess.run"):`` records (name, start, end) on
    the host clock and, while the profiler runs, writes the same span
    into the profiler's trace, where the idle gaps are named from it."""

    def __init__(self):
        self.records = []
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax.profiler

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                with self._lock:
                    self.records.append((name, t0, time.perf_counter()))


class TraceWindow:
    """The profiler over a slice of the measured window.  ``poll(now)``
    is called from the thread that drives the window (between steps, or
    from the waiting main thread of a serving cell)."""

    def __init__(self, enabled: bool, out_dir: str, start_after: float,
                 length: float):
        self.enabled = enabled
        self.out_dir = out_dir
        self.start_after = start_after
        self.length = length
        self.started = None
        self.stopped = None

    def poll(self, since_window_start: float):
        import jax.profiler

        if not self.enabled or self.stopped is not None:
            return
        if self.started is None:
            if since_window_start >= self.start_after:
                shutil.rmtree(self.out_dir, ignore_errors=True)
                jax.profiler.start_trace(self.out_dir)
                self.started = time.perf_counter()
        elif time.perf_counter() - self.started >= self.length:
            self.stop()

    def stop(self):
        import jax.profiler

        if self.enabled and self.started is not None \
                and self.stopped is None:
            jax.profiler.stop_trace()
            self.stopped = time.perf_counter()

    def xplane_path(self):
        found = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------

class Run:
    """What one run observed.  The entry fills ``e2e`` (end-to-end values
    by name), ``counters`` (anything a per-layer reader may want),
    ``checks`` (each number compared beside its limit) and the counts."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 devices, t_process_start: float):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.t0 = t_process_start
        self.watch = CompileWatch()
        self.spans = Spans()
        self.peaks = cell.peaks_table.get(
            devices[0].device_kind) if devices else None
        scratch = os.path.join(cell.root, ".bench_scratch")
        # one trace and one plan per cell, overwritten by the next run
        self.trace_dir = os.path.join(scratch, "trace", cell.name)
        t = cell.workload.get("trace", {})
        self.tracer = TraceWindow(self.trace, self.trace_dir,
                                  float(t.get("start_after_s", 0.0)),
                                  float(t.get("seconds", 3.0)))
        self.e2e = {}
        self.counters = {}
        self.checks = []         # (name, value, limit, ok)
        self.attempted = 0
        self.failed = 0
        self.not_counted_s = 0.0  # the reference's time, outside setup_s
        self.not_counted_compile = {}   # and what it compiled
        self.window_start = None
        self.trace_reduction = None

    # -- set-up bookkeeping ------------------------------------------------
    @contextlib.contextmanager
    def outside_setup(self):
        """The reference runs here: its seconds, and what it compiles,
        are not set-up."""
        t0, c0 = time.perf_counter(), self.watch.snapshot()
        try:
            yield
        finally:
            self.not_counted_s += time.perf_counter() - t0
            c1 = self.watch.snapshot()
            for k in c1:
                self.not_counted_compile[k] = (
                    self.not_counted_compile.get(k, 0) + c1[k] - c0[k])

    def begin_window(self):
        """Set-up ends here.  ``setup_s`` is process start to now, less
        what ran under :meth:`outside_setup`; the compilation counted as
        set-up leaves that out likewise.  The collector's automatic runs
        are held off until :meth:`end_window`: the window times the
        program, not when Python chooses to sweep the heap."""
        now = self.watch.snapshot()
        self.counters["setup_compile"] = {
            k: now[k] - self.not_counted_compile.get(k, 0) for k in now}
        self.watch.mark()
        gc.collect()
        gc.freeze()
        gc.disable()
        self.window_start = time.perf_counter()
        self.e2e["setup_s"] = (self.window_start - self.t0
                               - self.not_counted_s)
        return self.window_start

    def end_window(self):
        gc.enable()
        gc.unfreeze()
        self.counters["window_compile"] = self.watch.since_mark()

    # -- correctness ---------------------------------------------------------
    def check(self, name: str, value, limit, *, at_most: bool = True):
        """Record one compared number beside its limit and print it."""
        if value is None or value != value:       # missing or NaN
            ok = False
        else:
            ok = value <= limit if at_most else value >= limit
        self.checks.append((name, value, limit, ok))
        rel = "<=" if at_most else ">="
        print(f"check {name}: {value!r} {rel} {limit!r} -> "
              f"{'ok' if ok else 'NOT CORRECT'}", flush=True)
        return ok

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(ok for *_, ok in self.checks)


def percentile(values, q: float):
    """Nearest-rank percentile of a list (q in 0..100); None if empty."""
    if not values:
        return None
    s = sorted(values)
    k = max(0, min(len(s) - 1, int(-(-q * len(s) // 100)) - 1))
    return s[k]


def median(values):
    return statistics.median(values) if values else None


# ---------------------------------------------------------------------------
# per-layer readers and the result line
# ---------------------------------------------------------------------------

def _load_reader(cell: Cell, metric: str):
    """``metrics/<metric>.py`` or, for a quantity split by what its cells
    report (``device_idle_pct.train``, ``device_idle_pct.serve``), the one
    reader ``metrics/<quantity>.py`` before the last dot."""
    for name in dict.fromkeys((metric, metric.rsplit(".", 1)[0])):
        for base in (cell.dir, HERE):
            path = os.path.join(base, "metrics", name + ".py")
            if os.path.exists(path):
                spec = importlib.util.spec_from_file_location(
                    "benchmark_metric_" + name.replace(".", "_").replace(
                        "-", "_"), path)
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
                return mod.read
    raise SystemExit(f"benchmark: no reader benchmark/metrics/{metric}.py")


def per_layer_metrics(run: Run) -> dict:
    out = {}
    for row in run.cell.metric_rows("per_layer"):
        value = _load_reader(run.cell, row["name"])(run)
        if value is not None:
            out[row["name"]] = {"value": float(value), "unit": row["unit"]}
    return out


def end_to_end_metrics(run: Run) -> dict:
    out = {}
    for row in run.cell.metric_rows("end_to_end"):
        value = run.e2e.get(row["name"])
        if value is None:
            raise SystemExit(f"benchmark: {run.cell.name} did not measure "
                             f"{row['name']}")
        out[row["name"]] = {"value": float(value), "unit": row["unit"]}
    return out


def result_line(run: Run) -> dict:
    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": run.counters.get("memory_peak_bytes")}
    line = {"correct": run.correct, "attempted": int(run.attempted),
            "failed": int(run.failed)}
    if run.trace:
        red = run.trace_reduction
        if red is not None:
            device["busy_s"] = red.busy_s
            device["window_s"] = red.window_s
            line["breakdown"] = red.breakdown()
        line["metrics"] = per_layer_metrics(run)
    else:
        line["metrics"] = end_to_end_metrics(run)
    line["device"] = device
    # every number compared beside its limit, last in the line
    line["checks"] = {
        name: {"value": value if value is None or value == value
               else "nan", "limit": limit, "ok": ok}
        for name, value, limit, ok in run.checks}
    return line


def emit(line: dict):
    """The compared numbers as the last lines of standard error, then the
    result as the last line of standard output."""
    sys.stdout.flush()
    for name, c in line.get("checks", {}).items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'NOT CORRECT'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
