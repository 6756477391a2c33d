"""Step timelines: per-step records, host-side phase timers, profiler
spans.

Three instruments, one per time scale (docs/observability.md):

* :class:`StepRecord` / :class:`StepRecorder` — ONE structured record
  per training step: wall step time, host-phase breakdown (data_load /
  dispatch / blocking_fetch), throughput, the numerics guard's health
  summary when the host has it, and the cost model's PREDICTION for the
  active strategy (step time, exposed wire bytes, collective count) —
  the calibration bridge :mod:`autodist_tpu.telemetry.calibration`
  regresses against.  Records ride a bounded ring buffer and flush
  periodically as JSONL (rotated) into the run directory.
* :func:`host_span` — the LIVE span around a host-side phase (session
  step and its parts, set-up, the serving engine's tick): one
  ``jax.profiler.TraceAnnotation`` named ``autodist/<name>`` (so a
  capture window shows it on ``/host:CPU``, on the clock of the device
  planes) and one record in the process span ring
  (:func:`autodist_tpu.telemetry.profiler.get_span_writer`), from the
  same two ``perf_counter`` reads.  The span names are the constants
  below: the benchmark's readers match them.  A ``jax.monitoring``
  listener records what jax traces, lowers and compiles as
  ``compile/*`` spans in the same ring.
* :func:`sync_span` — ``jax.named_scope`` for code inside traced
  programs (the bucket sync legs in ``explicit_sync.py``/
  ``overlap.py``): named scopes prefix the lowered HLO ops, so a
  profiler trace attributes device time to reduce-scatter vs
  all-gather vs optimizer-update *by name*.  (A TraceAnnotation there
  would time TRACING, not execution — the two span helpers exist
  because the right tool differs inside vs outside ``jit``.)

Cost discipline: when telemetry is disabled, :meth:`StepRecorder.create`
returns None and every call site gates on that one identity check;
enabled, the per-step work is two ``perf_counter`` reads, one dataclass,
and two deque appends (the session's spans cost nothing measurable on
the chip: PERF.md, PR 24).  ``sync_span`` is trace-time-only metadata
and costs nothing per step on any path.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import deque
from dataclasses import asdict, dataclass, field
from typing import Any, Callable, Dict, List, Optional

from autodist_tpu.telemetry import flightrec, registry
from autodist_tpu.telemetry.events import emit_event
from autodist_tpu.telemetry.profiler import get_span_writer
from autodist_tpu.telemetry.registry import telemetry_enabled

#: JSONL rotation threshold: records per ``steps-*.jsonl`` segment.
ROTATE_RECORDS = 50_000
#: ring-buffer capacity (records kept in memory for snapshots/analysis).
RING_RECORDS = 1024
#: flush cadence (records between JSONL appends).
FLUSH_EVERY = 50


@dataclass
class StepRecord:
    """One training step, as the host saw it.

    ``phases`` holds seconds per host-side phase (``data_load``,
    ``dispatch``, ``blocking_fetch``, ...).  Health fields
    (``loss``/``all_finite``/``global_norm``/``loss_scale``/
    ``skipped_steps``) are filled only at points that already pay a
    host sync — fetching them per step would serialize dispatch.
    ``predicted_*``/``sync_bytes`` carry the analytic cost model's
    estimate for the active strategy, stamped once per session — the
    measured-vs-predicted pair every record contributes to calibration.
    """

    step: int
    time_unix: float
    step_time_s: Optional[float] = None
    phases: Dict[str, float] = field(default_factory=dict)
    items_per_s: Optional[float] = None
    tokens_per_s: Optional[float] = None
    loss: Optional[float] = None
    all_finite: Optional[bool] = None
    global_norm: Optional[float] = None
    loss_scale: Optional[float] = None
    skipped_steps: Optional[int] = None
    rolled_back: bool = False
    sync_bytes: Optional[float] = None          # predicted wire B/chip/step
    exposed_bytes: Optional[float] = None       # predicted exposed wire B
    num_collectives: Optional[int] = None
    predicted_step_time_s: Optional[float] = None
    # Short hash of the step's sync-schedule IR (docs/schedule-ir.md):
    # records stamped with a different fingerprint than the checkpoint
    # they resumed from executed a DIFFERENT schedule than planned.
    schedule_fingerprint: Optional[str] = None
    # Emitting host (stamped once per recorder) — the cross-host
    # aggregator keys per-host step-time skew and the trace exporter's
    # per-host tracks on it; None in records written before this field
    # existed.
    host: Optional[str] = None

    def to_json(self) -> str:
        d = {k: v for k, v in asdict(self).items() if v not in (None, {})}
        return json.dumps(d)

    @classmethod
    def from_dict(cls, d: dict) -> "StepRecord":
        known = {f for f in cls.__dataclass_fields__}  # noqa: C401
        return cls(**{k: v for k, v in d.items() if k in known})


class StepRecorder:
    """Per-session step-timeline recorder (see module docstring).

    ``predictor`` is a zero-arg callable returning the cost model's
    estimate dict (``time_s``/``wire_bytes``/``exposed_wire_bytes``/
    ``num_collectives``) or None; it is invoked lazily ONCE (first
    record) so sessions that never run pay nothing.
    """

    def __init__(self, run_id: str, directory: Optional[str] = None,
                 ring: int = RING_RECORDS, flush_every: int = FLUSH_EVERY,
                 rotate_records: int = ROTATE_RECORDS,
                 predictor: Optional[Callable[[], Optional[dict]]] = None):
        import socket

        self.run_id = run_id
        self._host = socket.gethostname()
        self._dir = directory
        self._ring: deque = deque(maxlen=max(ring, 1))
        self._unflushed: List[StepRecord] = []
        self._flush_every = max(int(flush_every), 1)
        self._rotate = max(int(rotate_records), 1)
        self._predictor = predictor
        self._predicted: Any = _UNSET
        self._pending_phases: Dict[str, float] = {}
        self._last_t: Optional[float] = None
        self._last_loss: Optional[float] = None
        self._file_index = 0
        self._lines_in_file = 0
        # Default-registry instrumentation (no-ops when disabled).
        from autodist_tpu.telemetry import registry as _reg
        self._m_steps = _reg.counter(
            "autodist_steps_total", "training steps run by this process")
        self._m_step_time = _reg.histogram(
            "autodist_step_time_seconds", "wall time between step ends")

    @classmethod
    def create(cls, run_id: str,
               predictor: Optional[Callable[[], Optional[dict]]] = None,
               directory: Optional[str] = None,
               **kwargs) -> Optional["StepRecorder"]:
        """The gated constructor: None when telemetry is disabled (call
        sites pay one identity check per step).  ``directory`` defaults
        to ``$AUTODIST_TELEMETRY_DIR/<run_id>`` when that env var is
        set; without it, records stay in the ring (no disk I/O)."""
        if not telemetry_enabled():
            return None
        if directory is None:
            from autodist_tpu.const import ENV
            base = ENV.AUTODIST_TELEMETRY_DIR.val
            if base:
                directory = os.path.join(base, run_id)
        return cls(run_id, directory=directory, predictor=predictor,
                   **kwargs)

    # -- phase timing ------------------------------------------------------
    def add_phase(self, name: str, seconds: float) -> None:
        """Accumulate host time into the NEXT record's phase ``name``."""
        self._pending_phases[name] = \
            self._pending_phases.get(name, 0.0) + seconds

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add_phase(name, time.perf_counter() - t0)

    # -- recording ---------------------------------------------------------
    def _prediction(self) -> Optional[dict]:
        if self._predicted is _UNSET:
            try:
                self._predicted = self._predictor() if self._predictor \
                    else None
            except Exception:   # prediction is advisory, never fatal
                self._predicted = None
        return self._predicted

    def record_step(self, step: int, *, items: Optional[int] = None,
                    tokens: Optional[int] = None) -> StepRecord:
        """Finalize one step: wall time since the previous record, the
        accumulated phases, throughput from ``items``/``tokens``."""
        now = time.perf_counter()
        dt = None if self._last_t is None else now - self._last_t
        self._last_t = now
        pred = self._prediction() or {}
        rec = StepRecord(
            step=int(step), time_unix=time.time(), step_time_s=dt,
            phases=self._pending_phases,
            items_per_s=(items / dt) if items and dt else None,
            tokens_per_s=(tokens / dt) if tokens and dt else None,
            sync_bytes=pred.get("wire_bytes"),
            exposed_bytes=pred.get("exposed_wire_bytes"),
            num_collectives=pred.get("num_collectives"),
            predicted_step_time_s=pred.get("time_s"),
            schedule_fingerprint=pred.get("schedule_fingerprint"),
            host=self._host)
        self._pending_phases = {}
        self._ring.append(rec)
        # Host-phase flight-recorder cursor (flightrec.py): the step
        # boundary is the coarsest progress beacon — the one every path
        # (GSPMD included) gets for free.  The session stamps the
        # matching "enter" before dispatch.
        flightrec.record_cursor("step", kind="phase", event="exit",
                                step=int(step))
        self._m_steps.inc()
        if dt is not None:
            self._m_step_time.observe(dt)
        if self._dir is not None:
            self._unflushed.append(rec)
            if len(self._unflushed) >= self._flush_every:
                self.flush()
        return rec

    def annotate(self, step: Optional[int] = None, **fields: Any) -> None:
        """Attach host-synced observations (loss, GradHealth summary,
        rollback flags) to the record for ``step`` (default: the most
        recent).  Searches the ring from the newest end — annotations
        always target a recent step."""
        target = None
        for rec in reversed(self._ring):
            if step is None or rec.step == step:
                target = rec
                break
        if target is None:
            return
        for k, v in fields.items():
            if hasattr(target, k) and v is not None:
                setattr(target, k, v)
        if fields.get("loss") is not None:
            self._last_loss = float(fields["loss"])

    # -- views -------------------------------------------------------------
    @property
    def records(self) -> List[StepRecord]:
        return list(self._ring)

    @property
    def directory(self) -> Optional[str]:
        return self._dir

    def snapshot(self) -> Optional[dict]:
        """A tiny host-cheap summary of the latest step — what heartbeat
        beacons carry so the monitor can report what a worker was DOING
        when it died (resilience/heartbeat.py).  Never touches device
        arrays."""
        if not self._ring:
            return None
        rec = self._ring[-1]
        out: Dict[str, Any] = {"step": rec.step}
        if rec.step_time_s is not None:
            out["step_time_ms"] = round(rec.step_time_s * 1e3, 3)
        loss = rec.loss if rec.loss is not None else self._last_loss
        if loss is not None:
            out["loss"] = round(float(loss), 6)
        return out

    # -- persistence -------------------------------------------------------
    def _segment_path(self) -> str:
        # Host in the filename (like events-*.jsonl): multi-host runs
        # share one directory over network FS, and two hosts can share
        # a pid.  The loader's steps-*.jsonl glob matches both formats.
        pid = os.getpid()
        safe = self._host.replace("/", "_").replace(":", "_")
        suffix = "" if self._file_index == 0 else f".{self._file_index}"
        return os.path.join(self._dir, f"steps-{safe}-{pid}{suffix}.jsonl")

    def flush(self) -> Optional[str]:
        """Append unflushed records as JSONL; rotates to a new segment
        every ``rotate_records`` lines.  Returns the segment path (None
        when there is no directory/nothing to write); never raises."""
        if self._dir is None or not self._unflushed:
            return None
        f = None
        try:
            os.makedirs(self._dir, exist_ok=True)
            path = self._segment_path()
            f = open(path, "a", encoding="utf-8")
            for rec in self._unflushed:
                f.write(rec.to_json() + "\n")
                self._lines_in_file += 1
                if self._lines_in_file >= self._rotate:
                    f.close()
                    self._file_index += 1
                    self._lines_in_file = 0
                    path = self._segment_path()
                    f = open(path, "a", encoding="utf-8")
            self._unflushed = []
            return path
        except OSError:
            self._unflushed = []
            return None
        finally:
            if f is not None:
                try:
                    f.close()
                except OSError:
                    pass


class _Unset:
    pass


_UNSET = _Unset()


# -- profiler spans ----------------------------------------------------------

#: every host span the program opens, in one place: ``host_span`` puts
#: ``SPAN_PREFIX`` before the name in the profiler's trace and the bare
#: name into the ring; ``benchmark/program_spans.py`` and the readers
#: under ``benchmark/metrics/`` match these strings.
SPAN_PREFIX = "autodist/"
SESSION_RUN = "session/run"                  # ids: step
SESSION_PLACE_BATCH = "session/place_batch"  # host batch -> device
SESSION_ENQUEUE = "session/enqueue"          # step_fn(...): step 0 compiles
SESSION_RECORD = "session/record"            # meter, StepRecord
SESSION_FETCH = "session/fetch"              # np.asarray(metrics), sync only
SETUP_BUILD_STRATEGY = "setup/build_strategy"
SETUP_COMPILE_STRATEGY = "setup/compile_strategy"
SETUP_PREFLIGHT = "setup/preflight"
SETUP_TRANSFORM = "setup/transform"
SETUP_PLACE_PARAMS = "setup/place_params"
SETUP_INIT_OPT_STATE = "setup/init_opt_state"
SETUP_INIT_SYNC_STATE = "setup/init_sync_state"
SETUP_ESTIMATE_COST = "setup/estimate_cost"
ENGINE_STEP = "engine/step"
ENGINE_HARVEST = "engine/harvest"
ENGINE_ADMIT = "engine/admit"
ENGINE_PREFILL = "engine/prefill"            # ids: rows, bucket, seen
ENGINE_DECODE_CHUNK = "engine/decode_chunk"  # ids: n, seen
ENGINE_HOST_SYNC = "engine/host_sync"        # the chunk's device->host wait
SERVER_LOCK_WAIT = "server/lock_wait"        # the driver's wait for the lock
SERVER_OBSERVE = "server/observe"
SERVER_SSE_POLL = "server/sse_poll"          # ids: request_id
ANALYSIS_CLI = "analysis/cli"
COMPILE_TRACE = "compile/trace"              # ids: fun_name
COMPILE_LOWER = "compile/lower"
COMPILE_BACKEND = "compile/backend"

#: scopes INSIDE traced code (``jax.named_scope``, not host spans): the
#: ONE list of names under which every device operation of a train step
#: falls.  A scope is a string in the lowered operations' metadata (XLA's
#: ``op_name``, the profiler's ``tf_op``) and costs a step nothing; a
#: Pallas call takes the last part of the INNERMOST scope as its HLO name,
#: so a kernel's own name (``attn``, ``sparse_attn``, ``window_attn``,
#: ``global_attn``) is entered inside ``*/attention``.  An operation
#: belongs to the innermost name of this list in its ``op_name``
#: (``benchmark/step_scopes.py`` reads the list from here): what the
#: layers' maps, checkpoints and autodiff add between the named parts
#: reads ``lm/layers`` and nothing deeper.  Every model factory enters
#: them (``tests/test_step_scopes.py`` walks the step's jaxpr); a new
#: part of a model gets a new name HERE.
SCOPE_LM_EMBED = "lm/embed"              # table lookup (and learned positions)
SCOPE_LM_LAYERS = "lm/layers"            # the whole stack, outside the rest
SCOPE_LM_HEAD_LOSS = "lm/head_loss"      # final norm, head, (chunked) loss
SCOPE_FFN_DENSE = "ffn/dense"            # a dense FFN: SwiGLU or GELU MLP
SCOPE_MHA_PROJECT = "mha/project"        # q, k, v, out of the flax block
SCOPE_MHA_ATTENTION = "mha/attention"    # its attention call alone
SCOPE_MLA_PROJECT = "mla/project"        # q, kv_a, kv_b, rotary, out
SCOPE_MLA_ATTENTION = "mla/attention"    # the attention call alone
SCOPE_GQA_PROJECT = "gqa/project"        # q, k, v, their norms, rotary, out
SCOPE_GQA_ATTENTION = "gqa/attention"    # gqa_attn, the plain call alone
SCOPE_DSA_INDEX = "dsa/index"            # the indexer's projections, scores
SCOPE_DSA_SELECT = "dsa/select"          # top-k of every row, packed words
SCOPE_DSA_ATTENTION = "dsa/attention"    # the attention call alone
SCOPE_SWA_ATTENTION = "swa/attention"    # window_attn or global_attn alone
SCOPE_BD_NOISE = "bd/noise"              # levels, masks, the noised copy
SCOPE_BD_ATTENTION = "bd/attention"      # bd_attn, the call alone
SCOPE_GATTN_ATTENTION = "gattn/attention"  # gated_attn, the call alone
SCOPE_GDN_PROJECT = "gdn/project"        # qkvz, ba, the gated norm, out
SCOPE_GDN_CONV = "gdn/conv"              # conv, silu, l2norm, z: gdn_conv
SCOPE_GDN_RECURRENCE = "gdn/recurrence"  # g, beta, gdn_scan
SCOPE_SCONV_PROJECT = "sconv/project"    # W_in, W_out of the short conv
SCOPE_SCONV_CONV = "sconv/conv"          # both gates and the three taps
SCOPE_MOE_ROUTE = "moe/route"            # scores, top-k, sort, group sizes
SCOPE_MOE_SHARED = "moe/shared"          # the shared experts (dense)
SCOPE_MOE_EXPERTS = "moe/experts"        # gather, grouped products, SwiGLU
SCOPE_MOE_COMBINE = "moe/combine"        # back to token order, weighted sum
SCOPE_STEP_OPTIMIZER = "step/optimizer"  # optimizer.update, apply_updates
SCOPE_STEP_GRAD_HEALTH = "step/grad_health"  # the guard: health, clip, select

#: jax's monitoring events -> the span each becomes (and its ``stage``
#: label on ``autodist_compile_seconds_total``).
_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": COMPILE_TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": COMPILE_LOWER,
    "/jax/core/compile/backend_compile_duration": COMPILE_BACKEND,
}

#: jax reports a trace for every call of a jitted function from a traced
#: one, microseconds each when its own cache answers, thousands of them
#: under one step function: those are counted, and only an event of a
#: millisecond or more becomes a span (they would flush the ring)
_COMPILE_SPAN_MIN_S = 1e-3

#: how many ended traces a thread remembers while it looks for the trace
#: that enclosed them (a step function calls some hundreds of jitted
#: functions; each is traced once)
_TRACES_REMEMBERED = 1024

_NULL_SPAN = contextlib.nullcontext()
_live = threading.local()        # .stack: this thread's open host spans
_listener_lock = threading.Lock()
_listening = False


def _stack() -> list:
    try:
        return _live.stack
    except AttributeError:
        _live.stack = []
        return _live.stack


class _HostSpan:
    """One live span: see :func:`host_span`.  ``start``/``end`` stay
    readable after the ``with`` (the session fills its step phases from
    them)."""

    __slots__ = ("name", "ids", "start", "end", "_parent", "_annotation")

    def __init__(self, name: str, ids: dict):
        self.name = name
        self.ids = ids
        self.start = self.end = None

    def __enter__(self):
        import jax

        stack = _stack()
        self._parent = stack[-1].name if stack else None
        stack.append(self)
        self._annotation = jax.profiler.TraceAnnotation(
            SPAN_PREFIX + self.name, **self.ids)
        self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.end = time.perf_counter()
        self._annotation.__exit__(*exc)
        _stack().pop()
        get_span_writer().record(self.name, start=self.start, end=self.end,
                                 parent=self._parent, **self.ids)
        return False


def host_span(name: str, **ids):
    """Live span around a host-side phase OUTSIDE traced code.

    On entry it reads ``time.perf_counter()`` and opens
    ``jax.profiler.TraceAnnotation("autodist/" + name, **ids)``: while a
    capture window (AUTODIST_TRACE_STEPS / AUTODIST_TRACE_AT, or any
    ``jax.profiler`` trace) is open the span is an event on
    ``/host:CPU``, on the device planes' clock; an inactive annotation
    is a flag test.  On exit it appends ``{name, start, end, parent,
    ids}`` to the process span ring (``profiler.get_span_writer()``;
    JSONL only under ``AUTODIST_TELEMETRY_DIR``).  ``parent`` is the
    enclosing span of the same thread; ``ids`` (``step=``,
    ``request_id=``) are what the spans of one step or request share.
    With ``AUTODIST_TELEMETRY=0`` it is the null context (``as`` gives
    None)."""
    if not telemetry_enabled():
        return _NULL_SPAN
    if not _listening:
        _listen_for_compiles()
    return _HostSpan(name, ids)


def _listen_for_compiles() -> None:
    """Register, once a process, the ``jax.monitoring`` listener behind
    the ``compile/*`` spans (jax has no unregister: the listener itself
    honours the telemetry switch)."""
    global _listening
    import jax.monitoring

    with _listener_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_compile_event)
            _listening = True


def _recompile_site(stack: list):
    """The live span under which a backend compile is a REPEAT: a
    ``session/run`` past step 0, or an engine dispatch whose shape
    already ran (``seen`` > 0).  None for a first compile."""
    for span in reversed(stack):
        if span.name == SESSION_RUN:
            return span if span.ids.get("step", 0) > 0 else None
        if span.name in (ENGINE_PREFILL, ENGINE_DECODE_CHUNK):
            return span if span.ids.get("seen", 0) > 0 else None
    return None


def _own_trace_seconds(start: float, seconds: float) -> float:
    """jax times the tracing of a jitted function called from a traced
    one inside its caller's time too.  The ended traces of this thread
    that began after ``start`` ended inside this one: take them off."""
    ended = getattr(_live, "traces", None)
    if ended is None:
        ended = _live.traces = []
    own = seconds
    while ended and ended[-1][0] >= start:
        own -= ended.pop()[1]
    ended.append((start, seconds))
    del ended[:-_TRACES_REMEMBERED]
    return max(own, 0.0)


def _on_compile_event(event: str, seconds: float, **kwargs) -> None:
    """What a ``backend_compile_duration`` counter cannot say: WHEN jax
    traced, lowered and compiled WHAT.  Each event (of a millisecond or
    more) becomes a span ending now and lasting its seconds, child of
    the thread's innermost live span, and every event adds to
    ``autodist_compile_seconds_total{stage}`` (a trace's OWN seconds;
    the spans keep their full extent, so a reader takes their union).  A repeat compile (:func:`_recompile_site`)
    counts in ``autodist_recompiles_total`` and is journalled with the
    step and the function's name.  Never raises into jax's compile
    path."""
    name = _COMPILE_STAGES.get(event)
    if name is None or not telemetry_enabled():
        return
    try:
        end = time.perf_counter()
        start = end - seconds
        stack = _stack()
        fun_name = str(kwargs.get("fun_name", ""))
        if seconds >= _COMPILE_SPAN_MIN_S:
            get_span_writer().record(
                name, start=start, end=end,
                parent=stack[-1].name if stack else None,
                fun_name=fun_name)
        registry.counter(
            "autodist_compile_seconds_total",
            "seconds jax spent tracing, lowering and compiling (or "
            "fetching from the persistent cache), by stage",
            labels={"stage": name.split("/", 1)[1]}).inc(
                _own_trace_seconds(start, seconds)
                if name == COMPILE_TRACE else seconds)
        site = _recompile_site(stack) if name == COMPILE_BACKEND else None
        if site is not None:
            registry.counter(
                "autodist_recompiles_total",
                "backend compiles inside a training step after the "
                "first, or inside an engine dispatch whose shape had "
                "already run").inc()
            emit_event("compile/recompile", span=site.name,
                       fun_name=fun_name, seconds=seconds, **site.ids)
    except Exception:  # pragma: no cover - telemetry must not fail a compile
        pass


def sync_span(name: str):
    """Named scope for code INSIDE traced programs: prefixes the lowered
    HLO op names, so profiler traces attribute device time to the sync
    leg by name (``autodist_sync/<name>``).  Trace-time-only — zero
    per-step cost."""
    import jax

    return jax.named_scope(f"autodist_sync/{name}")


def load_step_records(run_dir: str) -> List[StepRecord]:
    """Every ``steps-*.jsonl`` record under ``run_dir`` (recursive),
    step/time-ordered — the CLI's and the calibrator's input."""
    import glob as _glob

    out: List[StepRecord] = []
    for path in sorted(_glob.glob(
            os.path.join(run_dir, "**", "steps-*.jsonl"), recursive=True)):
        try:
            with open(path, "r", encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        out.append(StepRecord.from_dict(json.loads(line)))
                    except (ValueError, TypeError):
                        continue
        except OSError:
            continue
    out.sort(key=lambda r: (r.time_unix, r.step))
    return out
