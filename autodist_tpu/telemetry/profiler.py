"""Schedule-aware profiler: per-leg measured timings + request spans.

PR 6's telemetry measures the step as ONE number and the calibration
bridge regresses two global constants from it; PR 7's schedule IR names
every collective leg (kind, bytes, dtype, axis, slot) and
``estimate_ir_cost`` prices them individually.  Prediction happens at
leg granularity, measurement at step granularity — so calibration
cannot tell a slow ring hop from a slow optimizer update, and the
numerics guard's overhead stays unattributed.  This module is
the measurement half of closing that gap (the Automap argument,
arXiv:2112.02958: search quality tracks measured, fine-grained
calibration):

* :class:`LegSample` — one measured timing for one schedule-IR leg,
  keyed by ``schedule_fingerprint`` + ``leg_id``, JSONL-persisted as
  ``legs-<host>-<pid>.jsonl`` next to the StepRecord stream.
* :class:`LegProfiler` — produces LegSamples two ways:

  - **timed micro-runs** (:meth:`LegProfiler.profile_ir`): the IR's
    legs are grouped by ``(kind, alg, dtype, compressor, axis,
    nbytes)`` and each group's representative operation (psum_scatter /
    all_gather / psum / one ppermute hop / an Adam-shaped update) is
    jitted at the leg's actual byte size on the session mesh and timed
    (interleaved warmup + min-of-repeats).  Every leg in the group gets
    the measured time — the per-leg resolution the calibration
    regression needs;
  - **profiler-trace parsing** (:meth:`LegProfiler.parse_trace`): when
    a jax profiler capture window exists (``AUTODIST_TRACE_STEPS`` /
    ``AUTODIST_TRACE_AT``), the ``autodist_sync/*`` named-scope spans
    the sync path already carries (explicit_sync.py / overlap.py /
    quant_ring.py) are read out of the Chrome-trace JSON and mapped to
    leg kinds — measured device time with zero extra instrumentation.

* spans (:func:`record_span` / :func:`load_spans`) — the process span
  ring and its ``spans-<host>-<pid>.jsonl`` stream.  Two forms, one
  record: :func:`~autodist_tpu.telemetry.timeline.host_span` around a
  live phase (session step, set-up, engine tick; also a
  ``TraceAnnotation`` on the profiler's clock), and
  :func:`record_span` after the fact for request lifecycles
  (router/server/scheduler: queue-wait, prefill, decode, whole request,
  tagged with a propagated trace id).  The trace exporter merges them
  into the same Chrome-trace file as training steps and leg samples
  (docs/observability.md).

Cost discipline: micro-runs are explicit calls outside the step loop
and trace parsing is offline, so neither costs a step anything.  The
span ring is the one thing here a
training step touches: ``host_span`` appends five records a step
(PERF.md has the measured cost on the chip).  Everything except
:meth:`profile_ir` imports without jax.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import socket
import threading
import time
from collections import deque
from dataclasses import asdict, dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

#: in-memory span ring size when no run directory is configured.
MEMORY_SPANS = 4096

#: micro-run timing defaults (interleaved; min over repeats).
MICRO_WARMUP = 2
MICRO_REPEATS = 10

#: sample sources.
SOURCE_MICROBENCH = "microbench"
SOURCE_TRACE = "trace"


@dataclass
class LegSample:
    """One measured timing for one schedule-IR leg.

    ``(schedule_fingerprint, leg_id)`` is the key that joins a sample
    back to the exact program that was measured; ``kind``/``alg``/
    ``dtype``/``compressor``/``axis``/``slot``/``nbytes`` are copied
    from the leg so the calibration regression (and the CLI compare
    report) never needs the IR in hand.  ``predicted_s`` carries the
    leg-priced cost-model estimate under the DEFAULT constants — the
    measured-vs-predicted pair at leg granularity."""

    schedule_fingerprint: str
    leg_id: str
    kind: str
    measured_s: float
    alg: str = ""
    dtype: str = "float32"
    compressor: str = "NoneCompressor"
    axis: str = ""
    slot: int = -1
    nbytes: int = 0
    predicted_s: Optional[float] = None
    source: str = SOURCE_MICROBENCH
    host: str = ""
    time_unix: float = 0.0

    def to_json(self) -> str:
        d = {k: v for k, v in asdict(self).items() if v is not None}
        return json.dumps(d)

    @classmethod
    def from_dict(cls, d: dict) -> "LegSample":
        known = set(cls.__dataclass_fields__)
        return cls(**{k: v for k, v in d.items() if k in known})


def write_leg_samples(samples: Sequence[LegSample],
                      directory: str) -> Optional[str]:
    """Append samples as JSONL (``legs-<host>-<pid>.jsonl``) under
    ``directory``; returns the path (None on write failure — profiling
    must never kill the run)."""
    if not samples:
        return None
    host = socket.gethostname().replace("/", "_").replace(":", "_")
    path = os.path.join(directory, f"legs-{host}-{os.getpid()}.jsonl")
    try:
        os.makedirs(directory, exist_ok=True)
        with open(path, "a", encoding="utf-8") as f:
            for s in samples:
                f.write(s.to_json() + "\n")
        return path
    except OSError:
        return None


def load_leg_samples(run_dir: str) -> List[LegSample]:
    """Every ``legs-*.jsonl`` sample under ``run_dir`` (recursive),
    time-ordered — the calibrator's and the exporter's input.  Corrupt
    lines are skipped (a writer may have died mid-line)."""
    out: List[LegSample] = []
    for path in sorted(glob.glob(
            os.path.join(run_dir, "**", "legs-*.jsonl"), recursive=True)):
        try:
            with open(path, "r", encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        out.append(LegSample.from_dict(json.loads(line)))
                    except (ValueError, TypeError):
                        continue
        except OSError:
            continue
    out.sort(key=lambda s: (s.time_unix, s.leg_id))
    return out


# -- span-name -> leg-kind mapping (the autodist_sync/* vocabulary) ----------

#: named-scope prefix the sync path stamps (timeline.sync_span).
SYNC_SCOPE_PREFIX = "autodist_sync/"

#: ordered (name-fragment, leg-kind) rules for trace-span attribution —
#: first match wins; fragments mirror the sync_span call sites in
#: explicit_sync.py / overlap.py / quant_ring.py.
_SPAN_KIND_RULES: Tuple[Tuple[str, str], ...] = (
    ("quant_ring_fused/", "fused_hop"),
    ("fused_pack_detect", "fused_detect"),
    ("fused_shard_update", "fused_update"),
    ("ring_reduce_scatter/", "ppermute_hop"),
    ("ring_all_gather/", "ppermute_hop"),
    ("quant_ring_reduce_scatter/", "ppermute_hop"),
    ("quant_ring_all_gather/", "ppermute_hop"),
    ("param_gather/", "all_gather"),
    ("quant_all_gather", "all_gather"),
    ("guard_rollup", "psum_guard"),
    ("zero1_shard_update", "update"),
    ("tree_update", "update"),
    ("quant_all_to_all_reduce_scatter", "reduce_scatter"),
    ("moe_dispatch", "all_to_all"),
    ("moe_combine", "all_to_all"),
    ("expert_all_to_all", "all_to_all"),
    ("bucket_quant_reduce/", "all_reduce"),
    ("bucket_compressed_reduce/", "all_reduce"),
    ("bucket_reduce/", "all_reduce"),
    ("per_var_reduce/", "all_reduce"),
    ("one_shot_all_reduce", "all_reduce"),
)


def span_leg_kind(name: str) -> Optional[str]:
    """Leg kind an ``autodist_sync/*`` span name implies, or None for
    a name outside the sync vocabulary."""
    if SYNC_SCOPE_PREFIX in name:
        name = name.split(SYNC_SCOPE_PREFIX, 1)[1]
    for fragment, kind in _SPAN_KIND_RULES:
        if fragment in name:
            return kind
    return None


class LegProfiler:
    """Produce per-leg measured timings for a schedule IR.

    ``mesh`` (a ``jax.sharding.Mesh``) enables real collective
    micro-runs; without one (or on a degenerate axis) the group's
    operation runs locally — still a measurement of the host's compute/
    memory cost at the leg's byte size, which is what a single-process
    test environment can honestly provide.  Never raises from the
    measurement path: a group whose micro-program fails to build is
    skipped (profiling is advisory)."""

    def __init__(self, mesh: Any = None, *, warmup: int = MICRO_WARMUP,
                 repeats: int = MICRO_REPEATS):
        self._mesh = mesh
        self._warmup = max(int(warmup), 0)
        self._repeats = max(int(repeats), 1)
        self._host = socket.gethostname()

    # -- micro-runs --------------------------------------------------------
    def profile_ir(self, ir, *, include_update: bool = True
                   ) -> List[LegSample]:
        """Timed micro-runs over the IR's leg groups; one
        :class:`LegSample` per leg (legs in one group share the group's
        measured time).  ``predicted_s`` is stamped from the leg-priced
        cost model under the default constants."""
        from autodist_tpu.strategy.cost_model import leg_cost_s

        fingerprint = ir.fingerprint()
        groups: Dict[Tuple, List[Any]] = {}
        for leg in ir.legs:
            if leg.kind in ("update", "fused_update") and not include_update:
                continue
            key = (leg.kind, leg.alg, leg.dtype, leg.compressor,
                   leg.axis, int(leg.nbytes))
            groups.setdefault(key, []).append(leg)
        out: List[LegSample] = []
        now = time.time()
        for (kind, alg, dtype, compressor, axis, nbytes), legs \
                in groups.items():
            d = max(int(ir.axes.get(axis, 1)), 1) if axis else 1
            t = self._time_group(kind, dtype, nbytes, axis, d)
            if t is None:
                continue
            for leg in legs:
                out.append(LegSample(
                    schedule_fingerprint=fingerprint, leg_id=leg.id,
                    kind=kind, measured_s=t, alg=alg, dtype=dtype,
                    compressor=compressor, axis=axis, slot=int(leg.slot),
                    nbytes=int(nbytes),
                    predicted_s=leg_cost_s(leg, ir),
                    source=SOURCE_MICROBENCH, host=self._host,
                    time_unix=now))
        self._set_kind_gauges(out)
        return out

    def _time_group(self, kind: str, dtype: str, nbytes: int,
                    axis: str, d: int) -> Optional[float]:
        """Min-of-repeats wall time of one leg group's representative
        operation, or None when the micro-program cannot build."""
        try:
            fn, arg = self._build_micro(kind, dtype, nbytes, axis, d)
        except Exception:
            return None
        try:
            for _ in range(self._warmup):
                _block(fn(arg))
            best = None
            for _ in range(self._repeats):
                t0 = time.perf_counter()
                _block(fn(arg))
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            return best
        except Exception:
            return None

    def _build_micro(self, kind: str, dtype: str, nbytes: int,
                     axis: str, d: int):
        """(jitted fn, placed arg) for one leg group.  Collective kinds
        lower to their real primitive inside shard_map when the mesh
        has the axis at size > 1; otherwise (and for update legs) the
        micro-program is the equivalent local computation."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        dt = np.dtype(dtype)
        n = max(int(nbytes) // dt.itemsize, 1)
        mesh = self._mesh
        collective = kind in ("reduce_scatter", "all_gather", "all_reduce",
                              "ppermute_hop", "fused_hop", "psum_guard",
                              "ps_exchange", "all_to_all",
                              "hier_reduce_scatter", "dcn_all_reduce",
                              "dcn_exchange", "hier_all_gather")
        if collective and mesh is not None and axis \
                and int(dict(mesh.shape).get(axis, 1)) > 1:
            from jax.sharding import PartitionSpec as P

            d = int(dict(mesh.shape)[axis])
            n = ((n + d - 1) // d) * d
            if kind in ("reduce_scatter", "hier_reduce_scatter",
                        "dcn_exchange"):
                # The hier/dcn RS-shaped kinds run the same scatter
                # primitive — the micro-run times its wire on THIS
                # mesh's links (a CPU simulated-slice mesh has no DCN;
                # real per-tier constants come from pod traces).
                body = lambda x: jax.lax.psum_scatter(  # noqa: E731
                    x, axis, scatter_dimension=0, tiled=True)
                out_spec = P(axis)
            elif kind in ("all_gather", "hier_all_gather"):
                # per-device shard gathers back to the full vector
                body = lambda x: jax.lax.all_gather(  # noqa: E731
                    x, axis, tiled=True)
                out_spec = P()
            elif kind == "all_to_all":
                # MoE dispatch/combine: every device re-slices its
                # per-device capacity buffer across the expert axis —
                # the honest wire shape of the expert a2a pair.
                body = lambda x: jax.lax.all_to_all(  # noqa: E731
                    x.reshape(d, -1), axis, split_axis=0, concat_axis=0,
                    tiled=False).reshape(-1)
                out_spec = P(axis)
            elif kind in ("ppermute_hop", "fused_hop"):
                # A fused hop is still one ppermute on the wire; its
                # compute boundary rides the kernel, so the micro-run's
                # wire cost is the honest shared part.
                perm = [(i, (i + 1) % d) for i in range(d)]
                body = lambda x: jax.lax.ppermute(  # noqa: E731
                    x, axis, perm)
                out_spec = P(axis)
            else:  # all_reduce / psum_guard / ps_exchange / dcn_all_reduce
                body = lambda x: jax.lax.psum(x, axis)  # noqa: E731
                out_spec = P()
            fn = jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=P(axis), out_specs=out_spec,
                check_vma=False))
            arg = jnp.zeros((n,), dt)
            return fn, arg
        if kind in ("update", "fused_update"):
            # Adam-shaped: read param+2 slots, write param+2 slots — the
            # HBM-bound memory traffic the update leg models.  The
            # fused_update micro-run times the same arithmetic XLA-fused
            # (the kernel's one-pass cost on real TPU shows up in its
            # own fitted constant instead).
            def body(p):
                m = p * 0.9
                v = p * p * 0.999
                return p - 1e-3 * m / (jnp.sqrt(v) + 1e-8)
        elif kind == "fused_detect":
            # The guard statistics pass: one read of the bucket
            # producing both the finite count and the squared sum.
            def body(p):
                return (jnp.sum(p * p),
                        jnp.sum(1.0 - jnp.isfinite(p).astype(jnp.float32)))
        else:
            # Degenerate-axis collective: the data movement collapses;
            # time the local touch of the buffer (honest lower bound).
            def body(p):
                return p + p
        fn = jax.jit(body)
        arg = jnp.zeros((n,), dt if dt.kind == "f" else np.dtype("float32"))
        return fn, arg

    # -- trace parsing -----------------------------------------------------
    def parse_trace(self, trace_dir: str,
                    schedule_fingerprint: str = "") -> List[LegSample]:
        """LegSamples from the ``autodist_sync/*`` named-scope spans in
        a jax profiler capture under ``trace_dir`` (the
        ``AUTODIST_TRACE_STEPS``/``AUTODIST_TRACE_AT`` output): every
        ``*.trace.json[.gz]`` is searched recursively, Chrome-trace
        duration events whose names carry the sync vocabulary become
        samples with ``source="trace"``.  Device time attributed BY
        NAME — no extra per-step instrumentation."""
        out: List[LegSample] = []
        now = time.time()
        paths = sorted(
            glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                      recursive=True)
            + glob.glob(os.path.join(trace_dir, "**", "*.trace.json"),
                        recursive=True))
        for path in paths:
            try:
                opener = gzip.open if path.endswith(".gz") else open
                with opener(path, "rt", encoding="utf-8",
                            errors="replace") as f:
                    payload = json.load(f)
            except (OSError, ValueError):
                continue
            events = payload.get("traceEvents", payload) \
                if isinstance(payload, dict) else payload
            if not isinstance(events, list):
                continue
            for ev in events:
                if not isinstance(ev, dict):
                    continue
                name = str(ev.get("name", ""))
                kind = span_leg_kind(name)
                if kind is None or "dur" not in ev:
                    continue
                try:
                    dur_s = float(ev["dur"]) / 1e6
                except (TypeError, ValueError):
                    continue
                leg = name.split(SYNC_SCOPE_PREFIX, 1)[-1]
                out.append(LegSample(
                    schedule_fingerprint=schedule_fingerprint,
                    leg_id=leg, kind=kind, measured_s=dur_s,
                    source=SOURCE_TRACE, host=self._host, time_unix=now))
        self._set_kind_gauges(out)
        return out

    # -- gauges ------------------------------------------------------------
    def _set_kind_gauges(self, samples: Sequence[LegSample]) -> None:
        """Surface per-leg-kind measured (exposed) milliseconds as
        gauges on the process registry (docs/observability.md catalog:
        ``autodist_leg_exposed_ms{kind=...}``) — slotted legs before
        the final microbatch ride behind compute, so only end-of-step /
        final-slot samples count as exposed."""
        if not samples:
            return
        from autodist_tpu.telemetry import registry as _reg
        last_slot = max((s.slot for s in samples
                         if s.slot is not None and s.slot >= 0),
                        default=0)
        totals: Dict[str, float] = {}
        for s in samples:
            if s.slot is not None and 0 <= s.slot < last_slot:
                continue            # hidden behind the next microbatch
            totals[s.kind] = totals.get(s.kind, 0.0) + s.measured_s
        for kind, total in totals.items():
            _reg.gauge(
                "autodist_leg_exposed_ms",
                "measured exposed milliseconds per schedule-IR leg kind",
                labels={"kind": kind}).set(round(total * 1e3, 6))


def _block(x):
    import jax

    jax.block_until_ready(x)


# -- request spans (the serving trace plane) ---------------------------------

class _SpanWriter:
    """The process span ring and its JSONL writer
    (``spans-<host>-<pid>.jsonl``), modeled on the event journal:
    append-only, flushed per line, never raises, bounded in-memory ring
    without a run directory.

    ONE record shape for both span forms (docs/observability.md):
    ``name``, ``start``/``end`` on ``time.perf_counter()``'s clock (what
    the idle-gap readers and the step phases share), ``start_unix``/
    ``dur_s`` (what the cross-host trace export orders by), ``parent``
    (the enclosing live span of the same thread, None for an
    after-the-fact record), ``ids`` (``step=`` / ``request_id=`` and
    whatever else the site knows), ``trace_id``, ``host``, ``pid``.
    :func:`autodist_tpu.telemetry.timeline.host_span` is the live form;
    :func:`record_span` the after-the-fact one."""

    def __init__(self, directory: Optional[str] = None):
        self._dir = directory
        self._host = socket.gethostname()
        self._pid = os.getpid()
        self._lock = threading.Lock()
        self._memory: deque = deque(maxlen=MEMORY_SPANS)
        self._fh = None
        self._path: Optional[str] = None
        # perf_counter has no epoch; one reading of both clocks turns a
        # stamp of either into the other for the life of the process.
        self._unix_minus_perf = time.time() - time.perf_counter()
        if directory:
            safe = self._host.replace("/", "_").replace(":", "_")
            self._path = os.path.join(
                directory, f"spans-{safe}-{self._pid}.jsonl")

    @property
    def spans(self) -> List[dict]:
        with self._lock:
            return list(self._memory)

    def record(self, name: str, *, start: Optional[float] = None,
               end: Optional[float] = None,
               start_unix: Optional[float] = None,
               dur_s: Optional[float] = None, parent: Optional[str] = None,
               trace_id: str = "", **ids: Any) -> Optional[dict]:
        """Append one span, given either ``start``/``end``
        (``perf_counter``) or ``start_unix``/``dur_s`` (wall clock)."""
        if start is None:
            start = float(start_unix) - self._unix_minus_perf
            end = start + float(dur_s)
        rec: Dict[str, Any] = {
            "name": str(name), "start": float(start), "end": float(end),
            "parent": parent, "ids": ids, "trace_id": str(trace_id),
            "start_unix": float(start) + self._unix_minus_perf,
            "dur_s": float(end) - float(start),
            "host": self._host, "pid": self._pid}
        try:
            with self._lock:
                self._memory.append(rec)
                if self._path is not None:
                    if self._fh is None:
                        os.makedirs(os.path.dirname(self._path) or ".",
                                    exist_ok=True)
                        self._fh = open(self._path, "a", encoding="utf-8")
                    self._fh.write(json.dumps(rec, default=str) + "\n")
                    self._fh.flush()
            return rec
        except Exception:
            return None

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                try:
                    self._fh.close()
                except OSError:
                    pass
                self._fh = None


_spans: Optional[_SpanWriter] = None
_spans_lock = threading.Lock()


def _span_directory() -> Optional[str]:
    from autodist_tpu.const import ENV

    return ENV.AUTODIST_TELEMETRY_DIR.val or None


def get_span_writer() -> _SpanWriter:
    global _spans
    with _spans_lock:
        if _spans is None:
            _spans = _SpanWriter(directory=_span_directory())
        return _spans


def configure_spans(directory: Optional[str]) -> _SpanWriter:
    """(Re)point the process span writer at ``directory`` (None =
    in-memory only).  Closes the previous writer."""
    global _spans
    with _spans_lock:
        if _spans is not None:
            _spans.close()
        _spans = _SpanWriter(directory=directory)
        return _spans


def record_span(name: str, **span: Any) -> Optional[dict]:
    """Record one span after the fact on the process ring
    (:meth:`_SpanWriter.record`'s arguments): the form for intervals
    whose ends are only known once they are over (a request's queue
    wait, read at harvest).  ``start``/``end`` are ``perf_counter``
    stamps; a caller that holds only a wall-clock start gives
    ``start_unix``/``dur_s``.  Live phases use
    :func:`~autodist_tpu.telemetry.timeline.host_span`, which writes the
    same record.  No-op when telemetry is disabled; never raises (a full
    disk must not fail a request)."""
    from autodist_tpu.telemetry.registry import telemetry_enabled

    try:
        if not telemetry_enabled():
            return None
        return get_span_writer().record(name, **span)
    except Exception:  # pragma: no cover - defensive
        return None


def reset_spans_for_testing() -> None:
    global _spans
    with _spans_lock:
        if _spans is not None:
            _spans.close()
        _spans = None


def load_spans(run_dir: str) -> List[dict]:
    """Every ``spans-*.jsonl`` record under ``run_dir`` (recursive),
    start-time-ordered — the trace exporter's serving input."""
    out: List[dict] = []
    for path in glob.glob(os.path.join(run_dir, "**", "spans-*.jsonl"),
                          recursive=True):
        try:
            with open(path, "r", encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if isinstance(rec, dict):
                        out.append(rec)
        except OSError:
            continue
    out.sort(key=lambda r: r.get("start_unix", 0.0))
    return out
