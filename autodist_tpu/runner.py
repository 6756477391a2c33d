"""Execution layer: the distributed session.

Parity target: reference ``WrappedSession`` (``autodist/runner.py:33-132``) —
the object users run steps against — and the feed/fetch ``Remapper``
(``autodist/remapper.py:29-313``).  Functionally:

* feed remapping (split one host batch across replicas) becomes placing the
  global batch with the data-axis sharding;
* fetch remapping (gather per-replica outputs) is unnecessary — jitted
  outputs are already global arrays; ``.params`` gathers to host layout.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from autodist_tpu.graph_item import GraphItem
from autodist_tpu.kernel import sharding_utils as su
from autodist_tpu.kernel.graph_transformer import DistributedStep
from autodist_tpu.telemetry import flightrec, step_values
from autodist_tpu.telemetry import timeline as tl
from autodist_tpu.utils import logging, metrics, tracing


class DistributedSession:
    """Holds sharded training state and runs compiled steps.

    Like the reference's WrappedSession, construction places/initializes all
    state (the reference ran initializers on construction, runner.py:86-100).
    """

    def __init__(self, graph_item: GraphItem, dist_step: DistributedStep):
        self._gi = graph_item
        self._step = dist_step
        with tl.host_span(tl.SETUP_PLACE_PARAMS):
            self._params = dist_step.place_params(graph_item.params)
        with tl.host_span(tl.SETUP_INIT_OPT_STATE):
            self._opt_state = dist_step.init_fn(self._params)
        with tl.host_span(tl.SETUP_INIT_SYNC_STATE):
            self._sync_state = dist_step.init_sync_state(self._params)
        self._step_count = 0
        self._meter = metrics.ThroughputMeter()
        # Shapes/dtypes of the last batch (on-demand FLOPs estimation)
        # and its (items, tokens); both rebuilt only when the shapes
        # change.  Retaining the real batch would pin multi-GB host
        # buffers for the session lifetime.
        self._last_batch = None
        self._batch_key = None
        self._batch_sizes = (None, None)
        self._flops_per_step: Optional[float] = None
        # Tracing/dumps (SURVEY §5.1): keyed by the strategy id, the same
        # run identifier the reference used for its artifact paths.
        self._run_id = dist_step.compiled_strategy.strategy.id
        self._tracer = tracing.RunTracer(self._run_id)
        # Telemetry (docs/observability.md): one StepRecord per step —
        # wall step time, host-phase breakdown, and the cost model's
        # prediction for this strategy (the calibration bridge).  None
        # when AUTODIST_TELEMETRY=0, so the hot loop pays one identity
        # check.
        from autodist_tpu.telemetry.timeline import StepRecorder
        self._telemetry = StepRecorder.create(self._run_id,
                                              predictor=self._predict_cost)
        # Flight recorder (docs/observability.md "Flight recorder"):
        # stamp the schedule fingerprint onto this process's cursors,
        # publish the IR into the run dir so the chief can localize
        # hangs against the exact program, and arm the fatal paths
        # (faulthandler stacks + crash-bundle-on-uncaught).  Advisory:
        # any failure here must not block training.
        try:
            ir = getattr(dist_step, "schedule_ir", None)
            if ir is not None and flightrec.enabled():
                flightrec.set_fingerprint(ir.fingerprint())
                if self._telemetry is not None \
                        and self._telemetry.directory:
                    flightrec.publish_ir(ir, self._telemetry.directory)
                    flightrec.install_fatal_handlers(
                        self._telemetry.directory)
        except Exception:  # pragma: no cover - advisory only
            pass
        if tracing.dumps_enabled():
            tracing.dump_stage(self._run_id, "1-strategy-plans",
                               tracing.plan_table(dist_step.compiled_strategy))
            from autodist_tpu.utils import visualization
            visualization.log_shardings(self)

    # -- state -------------------------------------------------------------
    @property
    def params(self):
        """Current parameters, gathered to host numpy in the original
        single-device LOGICAL layout (pad rows stripped — the reference's
        checkpoint-compatibility invariant, checkpoint/saver.py:42-58)."""
        return self._step.unpad_host(su.host_local(self._params))

    @property
    def sharded_params(self):
        """Device-resident parameters in the step's PHYSICAL layout (padded
        when pad-to-divisible sharding is active)."""
        return self._params

    def export_state(self):
        """(params, opt_state) as sharded device arrays in the LOGICAL
        layout — what checkpoints store, so they interchange with
        single-device programs and across mesh topologies."""
        return (self._step.export_params(self._params),
                self._step.export_opt_state(self._opt_state))

    def import_state(self, params, opt_state, step: int = 0,
                     sync_state=None) -> None:
        """Load LOGICAL-layout state (e.g. from a checkpoint): params and
        optimizer state are padded/re-placed to the physical layout."""
        self._params = self._step.place_params(params)
        self._opt_state = self._step.import_opt_state(opt_state)
        self._sync_state = (sync_state if sync_state is not None
                            else self._step.init_sync_state(self._params))
        self._step_count = step

    @property
    def opt_state(self):
        return self._opt_state

    @property
    def sync_state(self):
        """Per-device synchronizer state (compressor residuals etc.); empty
        dict on the GSPMD path."""
        return self._sync_state

    @property
    def step_count(self) -> int:
        return self._step_count

    @property
    def mesh(self):
        return self._step.mesh

    @property
    def data_axis_size(self) -> int:
        from autodist_tpu.const import MESH_AXIS_DATA

        return int(self._step.mesh.shape.get(MESH_AXIS_DATA, 1))

    @property
    def schedule_fingerprint(self):
        """Short hash of the step's sync-schedule IR
        (docs/schedule-ir.md), or None for steps built before the IR
        existed.  Stamped into telemetry StepRecords and checkpoint
        meta so planned-vs-executed schedule drift is detectable across
        resume and elastic resize."""
        ir = getattr(self._step, "schedule_ir", None)
        try:
            return ir.fingerprint() if ir is not None else None
        except Exception:   # pragma: no cover - advisory only
            return None

    @property
    def schedule_ir(self):
        """The step's sync-schedule IR (docs/schedule-ir.md)."""
        return getattr(self._step, "schedule_ir", None)

    @property
    def zero1_buckets(self):
        """The ZeRO-1 flat-bucket plan of the compiled step (empty unless
        the explicit reduce-scatter path is active).  Checkpoints record
        it so elastic resume can reslice the flat optimizer shards at a
        different data-axis size (``resilience/elastic.py``)."""
        return tuple(getattr(self._step, "zero1_buckets", ()) or ())

    # -- running -----------------------------------------------------------
    def place_batch(self, batch: Any) -> Any:
        """Pre-place a host batch with the strategy's input shardings.
        Re-running a pre-placed batch skips the host→device transfer — use
        for input pipelines that prefetch (placing an already-placed batch
        is a no-op)."""
        return self._step.place_batch(batch)

    def place_local_batch(self, local_batch: Any) -> Any:
        """Assemble a global batch from this PROCESS-LOCAL shard (each host
        reads disjoint rows; leading dims concatenate over the data axis) —
        the multi-host input-pipeline path.  See
        :meth:`DistributedStep.place_local_batch`."""
        return self._step.place_local_batch(local_batch)

    def run(self, batch: Any, sync: bool = True) -> Dict[str, Any]:
        """Run one training step on a global batch.

        The batch is split along its leading dimension across the data axis
        (the Remapper's polymorphic-dim splitting, remapper.py:81-123).
        Returns metrics (at least ``{"loss": ...}``) — as host numpy when
        ``sync`` (the default), or as device arrays when ``sync=False`` so
        back-to-back steps dispatch asynchronously without a host round-trip
        per step."""
        rec = self._telemetry
        step_index = self._step_count
        with tl.host_span(tl.SESSION_RUN, step=step_index):
            t0 = time.perf_counter() if rec is not None else 0.0
            # Host-phase flight-recorder cursor: "entered step N" — the
            # coarsest progress beacon, paired with the "exit" stamp
            # record_step makes.  One object + one ring store when enabled.
            flightrec.record_cursor("step", kind="phase", event="enter",
                                    step=step_index)
            with tl.host_span(tl.SESSION_PLACE_BATCH,
                              step=step_index) as placed:
                batch = self._step.place_batch(batch)
            if step_index == 0 and tracing.dumps_enabled():
                self._dump_programs(batch)
            with self._tracer.step(step_index), tl.host_span(
                    tl.SESSION_ENQUEUE, step=step_index) as enqueued:
                self._params, self._opt_state, self._sync_state, out = \
                    self._step.step_fn(self._params, self._opt_state,
                                       self._sync_state, batch)
            self._tracer.after_step(step_index)
            self._step_count += 1
            record = None
            with tl.host_span(tl.SESSION_RECORD, step=step_index):
                self._note_batch(batch)
                self._meter.tick()
                if rec is not None:
                    # Dispatch time is the host-side cost of issuing the
                    # step (async: excludes device execution — the wall
                    # step_time_s converges to true step time once the
                    # pipeline fills); place_batch and enqueue are its
                    # two named parts, from the spans' own clock reads.
                    rec.add_phase("dispatch", time.perf_counter() - t0)
                    for phase, span in (("place_batch", placed),
                                        ("enqueue", enqueued)):
                        if span is not None:
                            rec.add_phase(phase, span.end - span.start)
                    items, tokens = self._batch_sizes
                    record = rec.record_step(step_index, items=items,
                                             tokens=tokens)
            # what the loss function emitted beside its loss goes to its
            # publishers with the fetch, never to the caller
            emitted = out.pop(step_values.KEY, None)
            if not sync:
                return out
            with tl.host_span(tl.SESSION_FETCH, step=step_index) as fetched:
                out, emitted = jax.tree_util.tree_map(
                    lambda x: np.asarray(x), (out, emitted))
            if emitted:
                self._step.step_values.publish(emitted)
            if record is not None and fetched is not None:
                # the wait for the device, which no part of dispatch holds
                record.phases["fetch"] = fetched.end - fetched.start
            return out

    def _note_batch(self, batch) -> None:
        """Keep the placed batch's shapes/dtypes and its (items, tokens):
        items = leading dim; tokens = rows x seq for a 2-D integer leaf
        (token ids) when one exists.  A steady loop feeds one shape, so
        the tree is rebuilt only when a leaf's shape or dtype differs."""
        leaves, treedef = jax.tree_util.tree_flatten(batch)
        key = (treedef, tuple((np.shape(x), x.dtype) for x in leaves))
        if key == self._batch_key:
            return
        self._batch_key = key
        abstract = [jax.ShapeDtypeStruct(shape, dtype)
                    for shape, dtype in key[1]]
        self._last_batch = jax.tree_util.tree_unflatten(treedef, abstract)
        items = tokens = None
        for leaf in abstract:
            shape = leaf.shape
            if not shape:
                continue
            if items is None:
                items = int(shape[0])
            if (tokens is None and len(shape) == 2
                    and np.issubdtype(leaf.dtype, np.integer)):
                tokens = int(shape[0]) * int(shape[1])
        self._batch_sizes = (items, tokens)

    @functools.cached_property
    def cost_report(self):
        """The cost model's estimate for this session's strategy on a
        spec synthesized from the mesh it runs on.  Computed once
        (``create_distributed_session`` logs it, every StepRecord
        carries it).  Advisory: None when the model cannot price the
        strategy."""
        try:
            from autodist_tpu.resource_spec import ResourceSpec
            from autodist_tpu.strategy.cost_model import estimate_cost

            n = int(self.mesh.devices.size)
            spec = ResourceSpec(resource_info={"nodes": [
                {"address": "localhost", "chips": n, "chief": True}]})
            return estimate_cost(
                self._step.compiled_strategy.strategy, self._gi, spec)
        except Exception:
            return None

    def _predict_cost(self) -> Optional[dict]:
        """:attr:`cost_report` as the fields stamped into every
        StepRecord (measured-vs-predicted is the calibration bridge,
        telemetry/calibration.py)."""
        report = self.cost_report
        if report is None:
            return None
        return {
            "time_s": report.time_s,
            "wire_bytes": report.wire_bytes,
            "exposed_wire_bytes": report.exposed_wire_bytes,
            "num_collectives": report.num_collectives,
            "schedule_fingerprint": self.schedule_fingerprint,
        }

    def lower_step(self, batch):
        """The training step lowered for ``batch`` (placed here if it is a
        host batch) and the current state: ``.as_text()`` is the traced
        StableHLO, ``.compile()`` the XLA executable whose ``as_text()``
        is what runs — sharded, fused, collectives inserted.  AOT
        compilation does not seed jit's dispatch cache; a step that
        already ran comes back from the persistent compilation cache
        where one is set."""
        return self._step.step_fn.lower(
            self._params, self._opt_state, self._sync_state,
            self._step.place_batch(batch))

    def _dump_programs(self, batch) -> None:
        """Staged program dumps at first run, when concrete shapes exist
        (see :meth:`lower_step`) — a debug-only second compile, paid only
        under AUTODIST_DUMP_GRAPHS=1."""
        lowered = self.lower_step(batch)
        tracing.dump_stage(self._run_id, "2-step-stablehlo",
                           lowered.as_text())
        try:
            compiled = lowered.compile()
            tracing.dump_stage(self._run_id, "3-step-optimized-hlo",
                               compiled.as_text())
        except Exception as e:  # pragma: no cover - backend-dependent
            logging.warning("optimized-HLO dump unavailable: %r", e)

    def evaluate(self, batches, sync: bool = True
                 ) -> Optional[Dict[str, Any]]:
        """Loss (and aux) on the CURRENT parameters with NO state change —
        the reference's fetch-only ``sess.run(loss)``.  ``batches`` is one
        batch dict or an iterable; an iterable returns the MEAN of every
        metric over batches (each batch weighted equally, numeric aux
        included).  Returns None for an empty iterable."""
        if isinstance(batches, dict):
            batches = [batches]
        acc, n = None, 0
        for b in batches:
            with tl.host_span(tl.SESSION_PLACE_BATCH):
                b = self._step.place_batch(b)
            out = self._step.eval_fn(self._params, b)
            acc = out if acc is None else jax.tree_util.tree_map(
                lambda a, x: a + x, acc, out)
            n += 1
        if acc is None:
            return None
        acc = jax.tree_util.tree_map(lambda a: a / n, acc)
        if not sync:
            return acc
        return jax.tree_util.tree_map(lambda x: np.asarray(x), acc)

    def run_many(self, batches) -> Dict[str, Any]:
        """Run a sequence of batches with async dispatch (no host round-trip
        per step); returns the last step's metrics on host."""
        out = None
        for b in batches:
            out = self.run(b, sync=False)
        if out is None:
            return None
        return jax.tree_util.tree_map(lambda x: np.asarray(x), out)

    def prefetch(self, batches, depth: int = 2):
        """Yield device-placed batches keeping ``depth`` host→device
        transfers in flight ahead of compute (device_put is async, so the
        next batch's copy overlaps the current step) — the device-side half
        of the input pipeline whose host side is
        :class:`autodist_tpu.runtime.data_loader.DataLoader`."""
        from collections import deque

        q: deque = deque()
        for b in batches:
            with tl.host_span(tl.SESSION_PLACE_BATCH):
                q.append(self.place_batch(b))
            if len(q) >= depth:
                yield q.popleft()
        while q:
            yield q.popleft()

    def run_epoch(self, batches, prefetch_depth: int = 2) -> Dict[str, Any]:
        """Run every batch of an epoch with device prefetch + async
        dispatch; returns the last step's metrics on host (None for an
        empty iterable)."""
        return self.run_many(self.prefetch(batches, prefetch_depth))

    def fit(self, data, **kwargs):
        """High-level epochs×steps training loop with callbacks, periodic
        logging, and checkpoint/resume — the reference's ``Model.fit``
        path (see :mod:`autodist_tpu.fit` for arguments)."""
        from autodist_tpu import fit as _fit

        return _fit.fit(self, data, **kwargs)

    # -- instrumentation (SURVEY §5: the reference only measured throughput
    # in example scripts; here it's a session feature) ----------------------
    @property
    def telemetry(self):
        """The session's :class:`~autodist_tpu.telemetry.timeline.
        StepRecorder` (None when AUTODIST_TELEMETRY=0).  One StepRecord
        per step; ``fit`` adds host-phase timings and health
        annotations; JSONL flushes under AUTODIST_TELEMETRY_DIR."""
        return self._telemetry

    def throughput(self, items_per_step: Optional[int] = None
                   ) -> Dict[str, Any]:
        """Sliding-window step timing: step_time_ms / steps_per_sec (+
        items_per_sec given a batch size).  With async dispatch this
        converges to true step time once the pipeline fills."""
        return self._meter.stats(items_per_step)

    def flops_per_step(self) -> Optional[float]:
        """Model FLOPs of the compiled step from XLA's cost analysis
        (cached — including the unavailable outcome, so polling mfu() never
        re-runs the AOT compile; needs at least one run).  None when
        unavailable."""
        if self._flops_per_step is None and self._last_batch is not None:
            flops = metrics.step_flops(
                self._step.step_fn, self._params, self._opt_state,
                self._sync_state, self._last_batch)
            # step_flops never yields 0.0 (it maps flops<=0 to None), so
            # False is an unambiguous unavailable-sentinel.
            self._flops_per_step = False if flops is None else flops
        if self._flops_per_step is None or self._flops_per_step is False:
            return None
        return self._flops_per_step

    def mfu(self) -> Optional[float]:
        """Model-FLOPs utilization of the last measurement window
        (None off-TPU / before 2 steps).  XLA's cost analysis reports
        PER-DEVICE flops for an SPMD program, so the denominator is a
        single chip's peak — the ratio is the whole mesh's utilization."""
        st = self._meter.step_time()
        if st is None:  # before the compile-triggering flops lookup
            return None
        flops = self.flops_per_step()
        if flops is None:
            return None
        return metrics.mfu(flops, st, [self.mesh.devices.flat[0]])

    def restore_targets(self):
        """Abstract (ShapeDtypeStruct + sharding) trees of the LOGICAL
        (params, opt_state) — the restore targets matching
        :meth:`export_state`'s layout."""
        st = self._step
        if st.pad_info is None:
            return (su.abstract_like(self._params),
                    su.abstract_like(self._opt_state))
        pa = jax.eval_shape(st.export_params, self._params)
        oa = jax.eval_shape(st.export_opt_state, self._opt_state)
        pa = jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            pa, st.logical_param_shardings)
        oa = jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            oa, st.logical_opt_shardings)
        return pa, oa

    def set_params(self, params) -> None:
        """Load new parameter values (e.g. from a checkpoint), re-placing
        them with the strategy's shardings.  Optimizer state is re-initialized."""
        self._params = self._step.place_params(params)
        self._opt_state = self._step.init_fn(self._params)
        # Seed from the NEW params — proxy caches must mirror the restored
        # values, not the capture-time ones.
        self._sync_state = self._step.init_sync_state(self._params)

    def load_state(self, params, opt_state, step: int = 0,
                   sync_state=None) -> None:
        """Full resume: params + optimizer state + step counter (+ optional
        synchronizer state, e.g. compressor residuals — without it, resume of
        a compressed run is approximate).  Values must already be
        placed/resharded."""
        self._params = params
        self._opt_state = opt_state
        self._sync_state = (sync_state if sync_state is not None
                            else self._step.init_sync_state(self._params))
        self._step_count = step
