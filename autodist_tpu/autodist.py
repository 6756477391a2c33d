"""User-facing facade.

Parity target: reference ``AutoDist`` (``autodist/autodist.py:297-322``) —
``AutoDist(resource_spec_file, strategy_builder)`` + ``scope()`` +
``create_distributed_session()`` / ``function()``.

TPU-native differences: the user *captures* the functional program explicitly
(``capture(params, optimizer, loss_fn)``) instead of the reference's implicit
graph+optimizer monkeypatch capture (``autodist/patch.py:40-116``); the
"session" holds sharded state and runs a jitted step rather than driving a TF
gRPC cluster.
"""
from __future__ import annotations

import contextlib
import itertools
from typing import Any, Callable, Dict, Optional, Sequence

from autodist_tpu.cluster import Cluster, make_cluster
from autodist_tpu.const import ENV
from autodist_tpu.coordinator import Coordinator
from autodist_tpu.graph_item import GraphItem
from autodist_tpu.kernel.graph_transformer import GraphTransformer
from autodist_tpu.mesh import build_mesh
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.runner import DistributedSession
from autodist_tpu.strategy.base import Strategy, StrategyBuilder
from autodist_tpu.strategy.compiler import StrategyCompiler
from autodist_tpu.telemetry import timeline as tl
from autodist_tpu.utils import logging

_default_autodist: Optional["AutoDist"] = None


def get_default_autodist() -> Optional["AutoDist"]:
    return _default_autodist


def _set_default_autodist(ad: "AutoDist") -> None:
    """One AutoDist per process (reference autodist.py:46-51); the guard is
    relaxed under AUTODIST_IS_TESTING so test matrices can re-instantiate."""
    global _default_autodist
    if _default_autodist is not None and not ENV.AUTODIST_IS_TESTING.val:
        raise RuntimeError("Only one AutoDist instance is allowed per process")
    _default_autodist = ad


class AutoDist:
    """Facade: resource spec + strategy builder → compiled distributed step.

    Args:
      resource_spec_file: yaml path (or pass ``resource_spec``); omitting both
        auto-derives a single-node spec from local devices.
      strategy_builder: a :class:`StrategyBuilder`; defaults to
        ``PSLoadBalancing`` (the reference's default, autodist.py:70).
      mesh_axes: optional logical mesh shape override, e.g.
        ``{"data": 4, "model": 2}``.
    """

    def __init__(self, resource_spec_file: Optional[str] = None,
                 strategy_builder: Optional[StrategyBuilder] = None,
                 resource_spec: Optional[ResourceSpec] = None,
                 mesh_axes: Optional[Dict[str, int]] = None):
        _set_default_autodist(self)
        self._resource_spec = resource_spec or ResourceSpec(resource_spec_file)
        if strategy_builder is None:
            from autodist_tpu.strategy.ps_lb_strategy import PSLoadBalancing
            strategy_builder = PSLoadBalancing()
        self._strategy_builder = strategy_builder
        self._mesh_axes = mesh_axes
        self._graph_item: Optional[GraphItem] = None
        self._session: Optional[DistributedSession] = None
        self._strategy: Optional[Strategy] = None
        self._in_scope = False
        self._cluster: Cluster = make_cluster(self._resource_spec)
        self._coordinator: Optional[Coordinator] = None
        self._implicit_record = None  # patch.CaptureRecord from the scope

    # -- capture -----------------------------------------------------------
    @contextlib.contextmanager
    def scope(self):
        """Context for building/capturing the model (reference
        autodist.py:309-322).  Marks the capture region, enforces the
        build-before-run ordering, and — unless ``AUTODIST_PATCH=False`` —
        installs the implicit-capture patches so a plain optax script is
        captured without calling :meth:`capture`
        (``autodist_tpu/patch.py``; reference ``autodist/patch.py:40-116``)."""
        from autodist_tpu.patch import PatchOptax

        self._in_scope = True
        patched = ENV.AUTODIST_PATCH.val
        if patched:
            PatchOptax.patch()
        try:
            yield self
        finally:
            self._in_scope = False
            if patched:
                self._implicit_record = PatchOptax.unpatch()

    def capture(self, params: Any, optimizer: Any = None,
                loss_fn: Optional[Callable] = None,
                sparse_vars: Sequence[str] = (),
                untrainable_vars: Sequence[str] = (),
                pipeline_vars: Sequence[str] = (),
                expert_vars: Sequence[str] = (),
                remat: Optional[str] = None,
                has_aux: bool = False,
                metrics_fn: Optional[Callable] = None,
                grad_fn: Optional[Callable] = None,
                accum_steps: int = 1,
                numerics=None) -> GraphItem:
        """Capture the training program (the explicit analog of the
        reference's optimizer/gradient monkeypatch hooks,
        graph_item.py:72-108).  ``metrics_fn(params, batch) -> dict``
        merges extra metrics (e.g. accuracy) into every step's and
        ``evaluate``'s outputs — the reference's extra ``sess.run``
        fetches / Keras ``compile(metrics=...)``.  ``accum_steps=N``
        accumulates gradients over N microbatches per step (effective
        batch B at the live activation memory of B/N for the gradient
        pass; a ``metrics_fn`` still runs one full-batch forward).  With
        ``has_aux`` the per-step aux comes back STACKED along a leading
        ``[N]`` axis (one entry per microbatch).

        ``numerics`` enables the numerics guard (docs/numerics.md):
        ``True`` for defaults (fused non-finite detection + skip +
        auto loss scaling), an ``on_nonfinite`` string
        (``"skip"|"raise"|"rollback"``), a dict of
        :class:`~autodist_tpu.numerics.NumericsConfig` fields (e.g.
        ``{"clip_norm": 1.0}`` for exact global-norm clipping), or a
        config instance.  Default None — no guard, byte-identical
        steps."""
        if self.is_built():
            raise RuntimeError(
                "Cannot capture after the distributed session was created "
                "(reference graph-mutation guard, autodist.py:152-165)")
        self._graph_item = GraphItem(
            params, optimizer=optimizer, loss_fn=loss_fn,
            sparse_vars=sparse_vars, untrainable_vars=untrainable_vars,
            pipeline_vars=pipeline_vars, expert_vars=expert_vars,
            remat=remat, has_aux=has_aux, metrics_fn=metrics_fn,
            grad_fn=grad_fn, accum_steps=accum_steps, numerics=numerics)
        return self._graph_item

    @property
    def graph_item(self) -> Optional[GraphItem]:
        return self._graph_item

    @property
    def resource_spec(self) -> ResourceSpec:
        return self._resource_spec

    def is_built(self) -> bool:
        return self._session is not None

    # -- build pipeline (reference autodist.py:139-150) --------------------
    def _assemble_implicit_graph_item(self) -> None:
        """Build the GraphItem from the scope's implicit capture record when
        ``capture()`` was never called (the reference's zero-code-change
        path, ``autodist/patch.py:40-116``)."""
        rec = self._implicit_record
        if rec is None or (rec.params is None and rec.optimizer is None
                           and rec.loss_fn is None):
            raise RuntimeError(
                "capture() the program before building a strategy (or build "
                "the optimizer/opt.init(params)/jax.value_and_grad(loss_fn) "
                "inside ad.scope() for implicit capture)")
        if not rec.complete():
            raise RuntimeError(
                "implicit capture inside ad.scope() is incomplete; missing: "
                + "; ".join(rec.missing()))
        logging.info("implicit capture: params + optax.%s + loss_fn %r",
                     rec.optimizer_factory,
                     getattr(rec.loss_fn, "__name__", rec.loss_fn))
        self._graph_item = GraphItem(
            rec.params, optimizer=rec.optimizer, loss_fn=rec.loss_fn,
            has_aux=rec.has_aux)

    def build_strategy(self) -> Strategy:
        """Chief builds the strategy; workers deserialize the chief's by id
        (reference _build_or_load_strategy, autodist.py:100-109)."""
        if self._graph_item is None:
            self._assemble_implicit_graph_item()
        self._graph_item.prepare()
        strategy_id = ENV.AUTODIST_STRATEGY_ID.val
        if strategy_id:
            logging.info("worker: loading strategy %s", strategy_id)
            self._strategy = Strategy.deserialize(strategy_id)
        else:
            with tl.host_span(tl.SETUP_BUILD_STRATEGY):
                self._strategy = self._strategy_builder.build(
                    self._graph_item, self._resource_spec)
                self._strategy.serialize()
        return self._strategy

    @property
    def cluster(self) -> Cluster:
        return self._cluster

    @property
    def coordinator(self) -> Optional[Coordinator]:
        return self._coordinator

    def _setup(self) -> None:
        """Chief-only multi-node bootstrap (reference _setup,
        autodist.py:120-128): fan the user script out to worker hosts, then
        join the distributed runtime.  Single-node: only Cluster.start()
        (a no-op)."""
        if (self._cluster.num_processes > 1
                and self._cluster.is_chief()
                and self._coordinator is None):
            self._coordinator = Coordinator(self._strategy, self._cluster)
            self._coordinator.launch_clients()
            import atexit
            # Chief reaps remote workers at exit (reference autodist worker
            # lifecycle, coordinator.py:92-110).  Bounded, so a chief-side
            # crash after launch terminates workers instead of hanging.
            atexit.register(self._coordinator.reap)
        self._cluster.start()

    def create_distributed_session(self, mesh=None,
                                   validate: Optional[bool] = None
                                   ) -> DistributedSession:
        """Full build pipeline: strategy → compile → transform → session
        (reference _create_distributed_session, autodist.py:167-185).

        ``mesh`` may be a Mesh or a zero-arg callable returning one: on
        multi-process runs the global device list only exists after the
        cluster rendezvous (``_setup`` → ``jax.distributed.initialize``),
        so a custom topology (e.g. ``build_hybrid_mesh``) must be built
        lazily — the callable runs after rendezvous.

        ``validate`` runs the static pre-flight analyzer
        (:mod:`autodist_tpu.analysis`) on the compiled strategy BEFORE
        any tracing: ERROR diagnostics raise
        :class:`~autodist_tpu.analysis.StrategyValidationError`
        immediately (a bad plan dies in milliseconds, not minutes into
        an XLA compile), WARNs log once.  Defaults to the
        ``AUTODIST_VALIDATE`` environment knob."""
        if self._session is not None:
            return self._session
        if self._strategy is None:
            self.build_strategy()
        self._setup()
        from jax.sharding import Mesh as _Mesh
        # NB: Mesh instances are themselves callable (context decorator),
        # so the factory check must exclude them explicitly.
        if mesh is not None and not isinstance(mesh, _Mesh) and callable(mesh):
            mesh = mesh()
        if mesh is None:
            mesh = build_mesh(self._mesh_axes, resource_spec=self._resource_spec)
        with tl.host_span(tl.SETUP_COMPILE_STRATEGY):
            compiled = StrategyCompiler(
                mesh, resource_spec=self._resource_spec).compile(
                    self._strategy, self._graph_item)
        if validate is None:
            validate = ENV.AUTODIST_VALIDATE.val
        if validate:
            from autodist_tpu.analysis import preflight

            with tl.host_span(tl.SETUP_PREFLIGHT):
                preflight(compiled, self._graph_item,
                          resource_spec=self._resource_spec,
                          context=f"build:{self._strategy.id}")
        with tl.host_span(tl.SETUP_TRANSFORM):
            dist_step = GraphTransformer(
                compiled, self._graph_item).transform(
                    extra_metrics_fn=self._graph_item.metrics_fn)
        self._session = DistributedSession(self._graph_item, dist_step)
        logging.info("distributed session created: strategy=%s mesh=%s",
                     self._strategy.id, dict(mesh.shape))
        with tl.host_span(tl.SETUP_ESTIMATE_COST):
            report = self._session.cost_report
        if report is not None:
            logging.info("estimated sync cost: %s", report.summary())
        return self._session

    # -- TF2-style one-liner (reference autodist.py:204-289) ---------------
    def function(self, fn: Optional[Callable] = None, *,
                 sync_every: int = 1):
        """Decorator parity with ``autodist.function``: wraps a per-batch
        step; the first call builds the session, later calls run steps.

        The decorated ``fn(batch)`` body is *declarative* in the reference
        (it defines the graph); here the captured loss_fn/optimizer define
        the step and ``fn``'s return value selects extra fetches from the
        metrics dict (or None for all metrics).

        Beyond fetch selection, the wrapper owns the hot-loop cadence the
        reference's remapper/session pairing owned: with ``sync_every=N``
        only every N-th call syncs metrics to host numpy; in between,
        steps dispatch back-to-back and return device arrays (JAX
        futures).  The per-step host round-trip is the classic accidental
        serializer on TPU (docs/performance.md); N≈10+ keeps dispatch
        ahead.  (Placement is already automatic: ``session.run`` places
        every batch, and placing a pre-placed/prefetched batch is a
        no-op.)

        Forms: bare ``@ad.function``, decorator factory
        ``@ad.function(sync_every=10)``, or ``ad.function()(None)`` /
        ``ad.function(sync_every=10)(None)`` for a plain step runner
        with no fetch selector.  (``ad.function()`` alone returns the
        decorator, not a runner — calling it with a batch raises.)
        """

        def wrap(user_fn):
            if user_fn is not None and not callable(user_fn):
                raise TypeError(
                    "ad.function()(...) expects a fetch-selector callable "
                    f"or None, got {type(user_fn).__name__}; to run a "
                    "step with no selector use ad.function()(None)")
            calls = itertools.count(1)

            def run_fn(batch):
                session = self.create_distributed_session()
                sync = sync_every <= 1 or next(calls) % sync_every == 0
                metrics = session.run(batch, sync=sync)
                out = user_fn(metrics) if user_fn is not None else metrics
                return out if out is not None else metrics
            return run_fn

        if fn is not None and not callable(fn):
            raise TypeError("ad.function expects a callable (or use @ad.function)")
        # Bare @ad.function gets the wrapped step directly; with only
        # kwargs (@ad.function(sync_every=N)) return the decorator.
        return wrap(fn) if fn is not None else wrap


def _reset_default_autodist_for_testing() -> None:
    global _default_autodist
    _default_autodist = None
