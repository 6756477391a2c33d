"""GraphTransformer: compile the captured program into a distributed step.

Parity target: reference ``autodist/kernel/graph_transformer.py:55-92`` which
orchestrates partition → replicate → in-graph sync → between-graph sync by
rewriting the TF graph.  TPU-natively all four phases collapse into *choosing
shardings and jitting once*:

* partitioning   → per-variable ``PartitionSpec`` (compiler VarPlan)
* replication    → the ``data`` mesh axis + batch sharding
* in-graph sync  → GSPMD-inserted ``psum`` over ``data`` when params are
                   replicated and the batch is sharded
* between-graph  → the same collectives ride DCN axes on multi-slice meshes;
  sync              weight-update sharding turns PS reduction into
                   reduce-scatter + sharded update + all-gather

The transformer emits a :class:`DistributedStep`: a jitted
``(params, opt_state, sync_state, batch) ->
(params, opt_state, sync_state, metrics)`` function with input/output
shardings bound and buffers donated (``sync_state`` carries per-device
synchronizer state such as compressor residuals; empty on the GSPMD path).
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from autodist_tpu.graph_item import GraphItem
from autodist_tpu.kernel import sharding_utils as su
from autodist_tpu.strategy.compiler import CompiledStrategy
from autodist_tpu.telemetry import step_values, timeline
from autodist_tpu.utils import logging


@dataclass
class DistributedStep:
    """The compiled training step plus everything needed to run it.

    ``step_fn(params, opt_state, sync_state, batch)`` →
    ``(params, opt_state, sync_state, metrics)``.  ``sync_state`` carries
    per-device synchronizer state (compressor residuals etc.); it is an empty
    dict on the GSPMD path.

    Pad-to-divisible sharding: when any variable carries a
    ``VarPlan.pad_axis``, the step's state is PHYSICAL (padded) and
    ``pad_info``/``opt_pad_info`` describe the boundary; ``place_params``
    pads logical → physical, ``export_*``/``unpad_host`` recover the
    logical view (so checkpoints keep the single-device interchange
    invariant).  ``pad_info is None`` ⇒ all of these are identities."""

    step_fn: Callable
    init_fn: Callable            # jitted physical params -> opt_state (sharded)
    init_sync_state: Callable    # (params?) -> sync-state pytree
    param_shardings: Any         # pytree of NamedSharding (physical layout)
    opt_shardings: Any
    mesh: Any
    compiled_strategy: CompiledStrategy
    eval_fn: Optional[Callable] = None  # (params, batch) -> metrics; no update
    # what the loss function emits beside its loss (telemetry/
    # step_values.py), to publish after a fetched step; None: nothing
    step_values: Any = None
    pad_info: Any = None             # params-shaped info tree, or None
    opt_pad_info: Any = None         # opt-state-shaped info tree, or None
    logical_param_shardings: Any = None  # pad axis dropped; None = physical
    logical_opt_shardings: Any = None
    # ZeRO-1 flat-bucket plan (explicit reduce-scatter path only; empty
    # elsewhere): checkpoints record it so elastic resume can reslice the
    # flat optimizer shards at a different data-axis size.
    zero1_buckets: Any = ()
    # The verified sync-schedule IR this step lowered (docs/schedule-ir.md)
    # — both paths build one; its fingerprint rides telemetry StepRecords
    # and checkpoint meta so planned-vs-executed drift is detectable.
    schedule_ir: Any = None
    _placer: Optional[Callable] = None
    _param_exporter: Optional[Callable] = None
    _opt_exporter: Optional[Callable] = None
    _opt_importer: Optional[Callable] = None

    def place_params(self, params):
        # A jitted pad+identity (not device_put): device_put may alias the
        # caller's buffers when layouts already match, and the step's
        # donation would then delete the user's original arrays.  Cached so
        # repeated placement (set_params/restore) compiles once.
        if self._placer is None:
            info = self.pad_info
            fn = (lambda p: su.pad_tree(p, info)) if info is not None \
                else (lambda p: p)
            self._placer = jax.jit(fn, out_shardings=self.param_shardings)
        return self._placer(params)

    # -- logical/physical boundary ----------------------------------------
    def export_params(self, phys_params):
        """Physical (padded) params → logical sharded arrays (pad axis
        gathered); identity when nothing is padded."""
        if self.pad_info is None:
            return phys_params
        if self._param_exporter is None:
            info = self.pad_info
            self._param_exporter = jax.jit(
                lambda p: su.unpad_tree(p, info),
                out_shardings=self.logical_param_shardings)
        return self._param_exporter(phys_params)

    def export_opt_state(self, opt_state):
        if self.pad_info is None:
            return opt_state
        if self._opt_exporter is None:
            info = self.opt_pad_info
            self._opt_exporter = jax.jit(
                lambda s: su.unpad_tree(s, info),
                out_shardings=self.logical_opt_shardings)
        return self._opt_exporter(opt_state)

    def import_opt_state(self, logical_opt_state):
        if self.pad_info is None:
            return logical_opt_state
        if self._opt_importer is None:
            info = self.opt_pad_info
            self._opt_importer = jax.jit(
                lambda s: su.pad_tree(s, info),
                out_shardings=self.opt_shardings)
        return self._opt_importer(logical_opt_state)

    def unpad_host(self, host_params):
        """Logical view of a host-gathered params tree (numpy in/out)."""
        if self.pad_info is None:
            return host_params
        return su.unpad_host_tree(host_params, self.pad_info)

    def place_batch(self, batch):
        def put(x, sh):
            import numpy as np
            if isinstance(x, np.ndarray) and not x.flags.owndata:
                # Non-owning views (e.g. the native DataLoader's ring-buffer
                # batches) must be copied on EVERY backend: the CPU backend
                # zero-copy aliases them, and on TPU device_put's host→HBM
                # DMA is ASYNC — the loader may recycle and rewrite the slot
                # while the transfer is still in flight (prefetch() exists
                # precisely to overlap those transfers with compute).
                # Reclaiming this copy requires synchronizing the loader's
                # slot release with transfer completion, not skipping it.
                x = np.array(x, copy=True)
            return jax.device_put(x, sh)

        return jax.tree_util.tree_map(
            put, batch, self.compiled_strategy.batch_shardings(batch))

    def place_local_batch(self, local_batch):
        """Assemble a GLOBAL batch from this process's LOCAL shard.

        ``place_batch`` requires every process to hold the identical global
        batch (the reference's feed model — the same feed_dict re-split by
        the Remapper, remapper.py:81-123).  Multi-host input pipelines
        instead read disjoint shards per host; this is the
        ``jax.make_array_from_process_local_data`` path: each process
        passes its local rows and the result is one global array whose
        leading dim is the concatenation over the data axis.  Scalars and
        already-placed leaves pass through."""
        import numpy as np

        # Sharding decisions (data-axis divisibility, seq-dim detection)
        # must see the GLOBAL shapes: leading dims are per-process here,
        # so scale them by process_count before consulting the strategy.
        pcount = jax.process_count()

        def global_like(x):
            shape = np.shape(x)
            if isinstance(x, jax.Array) or len(shape) == 0:
                return x
            return jax.ShapeDtypeStruct((shape[0] * pcount,) + shape[1:],
                                        np.asarray(x).dtype)

        shardings = self.compiled_strategy.batch_shardings(
            jax.tree_util.tree_map(global_like, local_batch))

        def put(x, sh):
            if isinstance(x, jax.Array):
                return x                      # already placed
            x = np.asarray(x)
            if x.ndim == 0:
                return jax.device_put(x, sh)  # scalars replicate
            if pcount > 1 and sh.spec == jax.sharding.PartitionSpec():
                # A replicated layout would stamp each process's DIFFERENT
                # local rows as "the same" global array — silent
                # cross-process divergence.  Replicated feeds must go
                # through place_batch with identical global data.
                raise ValueError(
                    "place_local_batch: this leaf lowers to a replicated "
                    f"layout (global shape {(x.shape[0] * pcount,) + x.shape[1:]} "
                    "does not shard on the data axis); feed it identically "
                    "on every process via place_batch instead")
            if not x.flags.owndata:
                x = np.array(x, copy=True)  # same DMA-lifetime rule as above
            return jax.make_array_from_process_local_data(sh, x)

        return jax.tree_util.tree_map(put, local_batch, shardings)


class GraphTransformer:
    """Builds a :class:`DistributedStep` from strategy + program."""

    def __init__(self, compiled_strategy: CompiledStrategy,
                 graph_item: GraphItem):
        self.compiled = compiled_strategy
        self.graph_item = graph_item

    # -- sharding trees ----------------------------------------------------
    def _param_specs(self) -> Dict[str, P]:
        return {name: plan.param_spec
                for name, plan in self.compiled.var_plans.items()}

    def _opt_specs(self) -> Dict[str, P]:
        return {name: plan.opt_spec
                for name, plan in self.compiled.var_plans.items()}

    def transform(self, extra_metrics_fn: Optional[Callable] = None
                  ) -> DistributedStep:
        gi = self.graph_item
        if gi.optimizer is None or gi.loss_fn is None:
            raise ValueError(
                "GraphItem must carry an optimizer and loss_fn to transform "
                "(capture them via AutoDist.capture)")
        mesh = self.compiled.mesh
        params = gi.params

        from autodist_tpu.const import MESH_AXIS_DATA
        from autodist_tpu.kernel.synchronization import explicit_sync
        if explicit_sync.uses_explicit_path(self.compiled):
            if gi.grad_fn is not None:
                raise ValueError(
                    "capture(grad_fn=...) cannot combine with gradient "
                    "compressors / fused groups (the explicit shard_map "
                    "path owns the gradient computation); drop the "
                    "compressor or the manual grad_fn")
            if mesh.shape.get(MESH_AXIS_DATA, 1) > 1:
                from autodist_tpu.kernel.synchronization.stale_sync import \
                    uses_stale_path
                if uses_stale_path(self.compiled):
                    logging.warning(
                        "strategy requests bounded staleness / proxy "
                        "variables AND gradient compression; the explicit "
                        "compressor path runs fully synchronous — "
                        "staleness/proxy settings are ignored")
                return self._transform_explicit(extra_metrics_fn)
            # No data axis ⇒ no gradient traffic to compress; the GSPMD path
            # is equivalent and supports arbitrary meshes.
            logging.info("compressors requested but mesh has no data axis; "
                         "using the GSPMD path (nothing to compress)")

        # Pad-to-divisible sharding: vars whose partitioned dim doesn't
        # divide the mesh axis are stored physically padded; the loss sees
        # the logical view through an unpad slice (autodiff then scatters
        # exactly-zero gradients into the pad rows).
        pad_map = {name: (axis, self.graph_item.info.by_name(name).shape[axis],
                          padded)
                   for name, (axis, padded) in self.compiled.pad_plans().items()}
        pad_info = su.pad_info_tree(params, pad_map) if pad_map else None
        if pad_info is not None:
            phys_params = jax.eval_shape(
                lambda p: su.pad_tree(p, pad_info), params)
            gi_loss = gi.loss_fn

            def loss_fn(p, batch):
                return gi_loss(su.unpad_tree(p, pad_info), batch)
            if extra_metrics_fn is not None:
                # metrics_fn, like the loss, sees the LOGICAL param view.
                user_metrics = extra_metrics_fn

                def extra_metrics_fn(p, batch):  # noqa: F811
                    return user_metrics(su.unpad_tree(p, pad_info), batch)
        else:
            phys_params = params
            loss_fn = gi.loss_fn

        param_spec_tree = su.spec_tree_for_params(params, self._param_specs())
        grad_spec_tree = su.spec_tree_for_params(params, self._opt_specs())
        param_sh = su.sharding_tree(mesh, param_spec_tree)
        # NamedSharding trees for in-step constraints: a bare PartitionSpec
        # needs an ambient mesh at trace time, which jit tracing doesn't have.
        grad_sh = su.sharding_tree(mesh, grad_spec_tree)

        # Freeze untrainable variables for real (zero updates, no
        # optimizer state) — see GraphItem.frozen_aware_optimizer.
        optimizer = gi.frozen_aware_optimizer(phys_params)

        # Optimizer-state layout: param-shaped blocks follow the per-variable
        # opt_spec (weight-update sharding for PS vars); scalars replicate.
        # Shapes are PHYSICAL (the state the step carries is padded).
        opt_shape = jax.eval_shape(optimizer.init, phys_params)
        opt_spec_tree = su.opt_spec_tree(opt_shape, phys_params, grad_spec_tree)
        opt_sh = su.sharding_tree(mesh, opt_spec_tree)

        # a loss function that emits step values hands them back as aux,
        # beside the user's own (telemetry/step_values.py)
        reports = None if gi.grad_fn is not None \
            else step_values.Collector.wanted_by(gi.loss_fn)
        train_loss_fn = loss_fn if reports is None \
            else reports.wrap(loss_fn, gi.has_aux)
        has_aux = gi.has_aux or reports is not None
        if gi.grad_fn is not None:
            # Manual value-and-grad (e.g. the 1F1B pipeline backward):
            # the contract is LOGICAL params in, LOGICAL grads out — under
            # pad-to-divisible sharding unpad on entry and zero-pad the
            # returned grads (pad rows stay untrained, matching the masked
            # update).
            user_grad = gi.grad_fn
            if pad_info is not None:
                def vg(p, batch):
                    loss, g = user_grad(su.unpad_tree(p, pad_info), batch)
                    return loss, su.pad_tree(g, pad_info)
            else:
                vg = user_grad
        else:
            vg = jax.value_and_grad(train_loss_fn, has_aux=has_aux)
        if gi.accum_steps > 1 and extra_metrics_fn is not None:
            logging.warning(
                "accum_steps=%d with metrics_fn: metrics run one "
                "FULL-batch forward in the same step, so peak "
                "activation memory stays O(batch) — the accumulation "
                "memory win applies to the gradient pass only",
                gi.accum_steps)

        # Bounded staleness / proxy mirrors ride in sync_state (see
        # stale_sync module; the SSP translation of the reference's token
        # queues, ps_synchronizer.py:385-455).
        from autodist_tpu.kernel.synchronization.stale_sync import (
            StaleSync, uses_stale_path)
        stale = StaleSync(gi, self.compiled) \
            if uses_stale_path(self.compiled) else None

        # Numerics guard on the GSPMD path (docs/numerics.md): grads are
        # already-global arrays here, so health is a fused local
        # reduction over the gradient tree (no extra collective — XLA
        # folds it into the update program).
        num_cfg = getattr(gi, "numerics", None)
        num_active = bool(num_cfg is not None and num_cfg.guard)
        num_ls = None
        injections: Dict[str, Any] = {}
        guard_mod = ls_mod = None
        if num_active and stale is not None:
            logging.warning(
                "numerics guard disabled: bounded-staleness/proxy sync "
                "state owns the sync_state slot on this path; drop "
                "staleness or route through the explicit bucketed path")
            num_active = False
        if num_active:
            import numpy as _np

            from autodist_tpu.numerics import guard as guard_mod
            from autodist_tpu.numerics import loss_scale as ls_mod

            dtypes = [str(_np.asarray(v).dtype)
                      for v in gi.name_to_leaf().values()]
            num_ls = ls_mod.resolve_loss_scale(num_cfg.loss_scale, dtypes)
            if num_ls is not None and gi.grad_fn is not None:
                logging.warning(
                    "numerics: loss scaling disabled — capture(grad_fn=...)"
                    " owns the backward pass, so the scale cannot be "
                    "threaded through it (guard/clip/skip stay active)")
                num_ls = None
            injections = guard_mod.resolve_injections(
                (), list(gi.name_to_leaf()))
            logging.info(
                "numerics guard: ON (GSPMD path, loss_scale=%s, "
                "clip_norm=%s, on_nonfinite=%s)",
                "off" if num_ls is None else "%g" % num_ls.init,
                num_cfg.clip_norm, num_cfg.on_nonfinite)
        else:
            from autodist_tpu.kernel.synchronization.explicit_sync import \
                chaos_grad_events_probe
            if list(chaos_grad_events_probe()):
                logging.warning(
                    "AUTODIST_CHAOS requests a gradient injection but the "
                    "numerics guard is off — nan_grad/inf_grad need "
                    "capture(numerics=...); ignoring the event")
        if num_active and num_ls is not None:
            def _scaled_loss(p, batch, scale):
                if has_aux:
                    loss_, aux_ = train_loss_fn(p, batch)
                    return loss_ * scale, aux_
                return train_loss_fn(p, batch) * scale
            vg_scaled = jax.value_and_grad(_scaled_loss, has_aux=has_aux)
        else:
            vg_scaled = None
        if gi.accum_steps > 1 and not num_active:
            vg = _accumulate_grads(vg, gi.accum_steps, has_aux)
        frozen_names = {v.name for v in gi.info.untrainable_variables}

        # Schedule IR (docs/schedule-ir.md): the GSPMD lowering of the
        # sync program — per-variable psum-tree collectives plus the
        # guard roll-up — built from the SAME plan facts the explicit
        # path buckets from, verified before tracing, and carried on the
        # step for telemetry/checkpoint fingerprints.
        from autodist_tpu.kernel.synchronization import schedule_ir as sir
        facts = []
        for name, plan in self.compiled.var_plans.items():
            vi = gi.info.by_name(name)
            if vi is None or name in frozen_names:
                continue
            facts.append(sir.fact_from_varplan(plan, vi))
        mesh_axes = {str(k): int(v) for k, v in dict(mesh.shape).items()}
        sched = sir.ir_from_facts(
            facts, axes=mesh_axes,
            accum_steps=gi.accum_steps, guard=num_active,
            moe=sir.moe_facts_from_vars(gi.info.variables, axes=mesh_axes))
        sir.assert_verified(sched, "gspmd build")

        def step(params, opt_state, sync_state, batch):
            # Trace-time mesh context: what the model zoo's default
            # attention reads to shard its Pallas kernel over this mesh
            # (jax will not partition a Mosaic custom call by itself: on
            # more than one device the step would not lower).
            with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                return _step(params, opt_state, sync_state, batch)

        def _step(params, opt_state, sync_state, batch):
            import jax.numpy as jnp

            params_in, opt_in = params, opt_state
            grad_params = params if stale is None \
                else stale.before_grads(params, sync_state)
            if num_active:
                from autodist_tpu.numerics.guard import NUMERICS_KEY
                ns = sync_state[NUMERICS_KEY]
                scale = ns["scale"] if num_ls is not None else None
                if scale is None:
                    vg_local = vg
                else:
                    vg_local = lambda p, b: vg_scaled(p, b, scale)  # noqa: E731
                if injections:
                    vg_local = guard_mod.wrap_injections(
                        vg_local, injections, ns["step"])
                if gi.accum_steps > 1:
                    vg_local = _accumulate_grads(vg_local, gi.accum_steps,
                                                 has_aux)
            else:
                scale = None
                vg_local = vg
            if has_aux:
                (loss, aux), grads = vg_local(grad_params, batch)
            else:
                loss, grads = vg_local(grad_params, batch)
                aux = None
            # Force the gradient layout the synchronizers chose: for PS/WUS
            # variables this lowers the data-axis reduction to
            # reduce-scatter; for sharded embeddings the scatter-add lands
            # on the owning shard.
            grads = su.constrain(grads, grad_sh)
            if stale is not None:
                grads, sync_state = stale.exchange(grads, sync_state)
            all_finite = gnorm = per_bucket = None
            if num_active:
                # Health over the (already-global) gradient tree — the
                # per-variable analog of the bucketed guard; frozen vars
                # are excluded (their updates are masked to zero anyway).
                from autodist_tpu.graph_item import path_name as _pn
                with jax.named_scope(timeline.SCOPE_STEP_GRAD_HEALTH):
                    health = guard_mod.HealthAccumulator(1)
                    for path, g in \
                            jax.tree_util.tree_flatten_with_path(grads)[0]:
                        if _pn(path) not in frozen_names:
                            health.add(_pn(path), g)
                    inv_scale = jnp.float32(1.0) if scale is None \
                        else jnp.float32(1.0) / scale
                    all_finite, gnorm, per_bucket = health.finalize(
                        (), loss, inv_scale)
                    mult = inv_scale
                    clip = guard_mod.clip_multiplier(gnorm,
                                                     num_cfg.clip_norm)
                    if clip is not None:
                        mult = mult * clip
                    if clip is not None or scale is not None:
                        grads = jax.tree_util.tree_map_with_path(
                            lambda p, g: g if _pn(p) in frozen_names
                            else (g.astype(jnp.float32)
                                  * mult).astype(g.dtype),
                            grads)
            with jax.named_scope(timeline.SCOPE_STEP_OPTIMIZER):
                updates, opt_state = optimizer.update(grads, opt_state,
                                                      params)
                params = optax.apply_updates(params, updates)
                if pad_info is not None:
                    # Keep pad rows exactly zero even for optimizers whose
                    # update is not zero-preserving (noise, non-zero decay).
                    params = su.mask_pad_tree(params, pad_info)
            # Fresh params return to their compute layout (all-gather for
            # WUS variables — "broadcast from the PS").
            params = su.constrain(params, param_sh)
            if stale is not None:
                sync_state = stale.after_update(params, sync_state)
            metrics = {"loss": loss}
            if num_active:
                from autodist_tpu.numerics import loss_scale as _lsm
                with jax.named_scope(timeline.SCOPE_STEP_GRAD_HEALTH):
                    params = guard_mod.tree_select(all_finite, params,
                                                   params_in)
                    opt_state = guard_mod.tree_select(all_finite, opt_state,
                                                      opt_in)
                new_ns = _lsm.update_state(ns, all_finite, num_ls)
                sync_state = dict(sync_state)
                sync_state[NUMERICS_KEY] = new_ns
                if scale is not None:
                    metrics["loss"] = loss * inv_scale
                metrics["grad_health"] = guard_mod.GradHealth(
                    all_finite=all_finite, global_norm=gnorm,
                    loss_scale=ns["scale"],
                    skipped_steps=new_ns["skipped"],
                    per_bucket=per_bucket)
            if reports is not None:
                aux, emitted = aux
                if emitted:
                    metrics[step_values.KEY] = emitted
            if aux is not None:
                metrics["aux"] = aux
            if extra_metrics_fn is not None:
                metrics = _merge_metrics(metrics, extra_metrics_fn(params,
                                                                   batch))
            return params, opt_state, sync_state, metrics

        # Batch shardings are per-leaf (data on dim 0, seq on dim 1 where it
        # applies) — leave them unspecified and let placed arguments carry
        # their own layout.
        sync_sh = None if stale is None \
            else stale.state_shardings(mesh, phys_params)
        jit_kwargs = {}
        combiner = self._combiner_bytes()
        from autodist_tpu.const import ENV
        flag = ENV.AUTODIST_COMBINER_FLAG.val
        if combiner and flag and mesh.devices.flat[0].platform == "tpu":
            # Strategy `group`/chunk_size lowered as XLA's all-reduce
            # combiner threshold: the compiler merges the grouped psums into
            # fused collectives — the TPU-native form of the reference's
            # scoped-allocator chunk merge (all_reduce_strategy.py:21-90).
            # Env-gated: XLA's DEFAULT combiner already merges
            # same-program psums (verified in HLO), so the flag only tunes
            # the threshold.  Set e.g.
            # AUTODIST_COMBINER_FLAG=xla_gpu_all_reduce_combine_threshold_bytes.
            jit_kwargs["compiler_options"] = {flag: combiner}
        step_fn = jax.jit(
            step,
            in_shardings=(param_sh, opt_sh, sync_sh, None),
            out_shardings=(param_sh, opt_sh, sync_sh, None),
            # Numerics state, like stale-sync state, is rewritten every
            # step — donation-safe.
            donate_argnums=(0, 1) if stale is None and not num_active
            else (0, 1, 2),
            **jit_kwargs,
        )

        # Same loss_fn as training (the pad-aware wrapper), so padded rows
        # contribute nothing to evaluation.
        eval_fn = jax.jit(
            _make_eval_step(loss_fn, gi.has_aux, extra_metrics_fn, mesh),
            in_shardings=(param_sh, None))
        init_fn = jax.jit(optimizer.init, out_shardings=opt_sh)
        if stale is None and num_active:
            def init_sync_state(current_params=None):
                from autodist_tpu.numerics import loss_scale as _lsm
                from autodist_tpu.numerics.guard import NUMERICS_KEY
                return {NUMERICS_KEY: _lsm.init_state(num_ls)}
        elif stale is None:
            def init_sync_state(current_params=None):
                return {}
        else:
            # Takes the CURRENT params (a set_params/checkpoint restore must
            # seed proxy caches from the restored values, not the capture-time
            # ones); jitted with out_shardings so the delay queue's zeros are
            # built shard-by-shard in place, never dense on one device.
            jit_init = jax.jit(stale.init_state, out_shardings=sync_sh)

            def init_sync_state(current_params=None):
                if current_params is None:
                    # The rare explicit-None path takes LOGICAL params.
                    current_params = params if pad_info is None \
                        else su.pad_tree(params, pad_info)
                return jit_init(current_params)

        # Logical-layout sharding trees (pad axis gathered) for the
        # checkpoint/export boundary — identical to physical when unpadded.
        opt_pad_info = logical_param_sh = logical_opt_sh = None
        if pad_info is not None:
            opt_pad_info = su.opt_spec_tree(opt_shape, phys_params, pad_info,
                                            default="")
            logical_param_specs = self._logical_specs(self._param_specs())
            logical_grad_specs = self._logical_specs(self._opt_specs())
            logical_param_sh = su.sharding_tree(
                mesh, su.spec_tree_for_params(params, logical_param_specs))
            opt_shape_logical = jax.eval_shape(optimizer.init, params)
            logical_opt_sh = su.sharding_tree(mesh, su.opt_spec_tree(
                opt_shape_logical, params,
                su.spec_tree_for_params(params, logical_grad_specs)))

        logging.info(
            "GraphTransformer: compiled step over mesh %s (%d vars: %s)",
            dict(mesh.shape), len(self.compiled.var_plans),
            _plan_summary(self.compiled))
        return DistributedStep(
            step_fn=step_fn, init_fn=init_fn,
            init_sync_state=init_sync_state,
            param_shardings=param_sh, opt_shardings=opt_sh,
            mesh=mesh, compiled_strategy=self.compiled,
            eval_fn=eval_fn, step_values=reports,
            pad_info=pad_info, opt_pad_info=opt_pad_info,
            logical_param_shardings=logical_param_sh,
            logical_opt_shardings=logical_opt_sh,
            schedule_ir=sched)

    def _combiner_bytes(self) -> int:
        """Largest collective-group byte sum — the all-reduce combiner
        threshold that lets XLA merge each strategy group into one fused
        collective.  0 when no group has ≥2 members (grouping inert)."""
        best = 0
        for names in self.compiled.fusable_groups().values():
            total = sum(self.graph_item.info.by_name(n).byte_size
                        for n in names)
            best = max(best, total)
        return best

    def _logical_specs(self, specs: Dict[str, P]) -> Dict[str, P]:
        """Per-variable specs with the pad axis entry dropped (the logical
        view cannot be sharded along a dim that doesn't tile evenly)."""
        from autodist_tpu.strategy.compiler import spec_from_entries

        out: Dict[str, P] = {}
        for name, spec in specs.items():
            plan = self.compiled.var_plans[name]
            if plan.pad_axis is None:
                out[name] = spec
                continue
            entries = list(spec)
            if plan.pad_axis < len(entries):
                entries[plan.pad_axis] = None
            out[name] = spec_from_entries(entries)
        return out

    def _transform_explicit(self, extra_metrics_fn: Optional[Callable] = None
                            ) -> DistributedStep:
        """Compressor-carrying programs run the whole step inside shard_map
        with manual collectives (see explicit_sync module docstring)."""
        from autodist_tpu.kernel.synchronization import explicit_sync

        gi = self.graph_item
        mesh = self.compiled.mesh
        # extra metrics run OUTSIDE shard_map, on the updated params and the
        # GLOBAL batch — identical semantics to the GSPMD path (inside the
        # mapped step they would see only the local data shard and get
        # pmean-averaged, silently changing non-mean metrics).
        (step_fn, init_fn, init_sync, param_sh, opt_sh, rs_buckets,
         sched) = explicit_sync.make_explicit_step(gi, self.compiled)
        if extra_metrics_fn is not None:
            inner_step = step_fn

            def wrapped(params, opt_state, sync_state, batch):
                params, opt_state, sync_state, metrics = inner_step(
                    params, opt_state, sync_state, batch)
                with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                    extra = extra_metrics_fn(params, batch)
                return (params, opt_state, sync_state,
                        _merge_metrics(metrics, extra))

            # Donation must live on the OUTER jit (the inner jit inlines
            # under tracing and its donate_argnums are ignored).
            step_fn = jax.jit(wrapped, donate_argnums=(0, 1, 2))
        eval_fn = jax.jit(
            _make_eval_step(gi.loss_fn, gi.has_aux, extra_metrics_fn, mesh))
        logging.info(
            "GraphTransformer: compiled EXPLICIT step over mesh %s (%d vars)",
            dict(mesh.shape), len(self.compiled.var_plans))
        return DistributedStep(
            step_fn=step_fn, init_fn=init_fn, init_sync_state=init_sync,
            param_shardings=param_sh, opt_shardings=opt_sh,
            mesh=mesh, compiled_strategy=self.compiled, eval_fn=eval_fn,
            zero1_buckets=tuple(rs_buckets), schedule_ir=sched)


def _make_eval_step(loss_fn: Callable, has_aux: bool,
                    metrics_fn: Optional[Callable], mesh) -> Callable:
    """Fetch-only metrics step (the reference's ``sess.run(loss)``): loss
    (+ captured ``metrics_fn`` extras) on the current params, no state
    change.  Traced under ``mesh``'s context like the training step."""
    def eval_step(params, batch):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            if has_aux:
                loss, aux = loss_fn(params, batch)
                out = {"loss": loss, "aux": aux}
            else:
                out = {"loss": loss_fn(params, batch)}
            if metrics_fn is not None:
                out = _merge_metrics(out, metrics_fn(params, batch))
            return out

    return eval_step


def _accumulate_grads(vg: Callable, accum: int, has_aux: bool) -> Callable:
    """Wrap a value-and-grad so one step averages gradients over ``accum``
    microbatches (leading-dim split) under a ``lax.scan`` — effective
    batch B at the live activation memory of B/accum.  Exact for row-mean
    losses (every bundled model): the mean of per-microbatch means equals
    the full-batch mean, and likewise for their gradients.  With
    ``has_aux`` the returned aux is STACKED along a leading [accum] axis.

    A leading dim that does not divide ``accum`` runs UNEVEN tail
    microbatches: the first ``dim % accum`` microbatches carry one extra
    row, the loop unrolls (shapes differ per microbatch, so no scan),
    and every contribution is weighted by its row count — still exactly
    the full-batch mean for row-mean losses.

    On the explicit compressor path this wrapper runs INSIDE shard_map,
    so the leading dim it splits is the device's LOCAL batch slice
    (global batch / data-axis size) — that is what must divide (or at
    least reach) accum.
    """
    from jax import lax

    def vg_accum(params, batch):
        leaves = jax.tree_util.tree_leaves(batch)
        dims = {leaf.shape[0] for leaf in leaves}
        if len(dims) != 1:
            raise ValueError(
                f"batch leaves disagree on the leading dim: {sorted(dims)}")
        (length,) = dims
        if length % accum:
            return _uneven_accumulate(vg, accum, has_aux, params, batch,
                                      length)
        mbs = jax.tree_util.tree_map(
            lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
            batch)

        def body(carry, mb):
            loss_acc, g_acc = carry
            if has_aux:
                (loss, aux), g = vg(params, mb)
            else:
                loss, g = vg(params, mb)
                aux = None
            g_acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(a.dtype), g_acc, g)
            return (loss_acc + loss.astype(jax.numpy.float32), g_acc), aux

        # f32 accumulators: microbatch grads may be bf16; summing accum of
        # them in bf16 loses low bits the single-pass computation keeps.
        # The final average casts back to the grad dtypes autodiff made.
        g_shapes = jax.eval_shape(lambda p, b: vg(p, b)[1], params,
                                  jax.tree_util.tree_map(
                                      lambda x: x[0], mbs))
        g0 = jax.tree_util.tree_map(
            lambda s: jax.numpy.zeros(s.shape, jax.numpy.float32), g_shapes)
        (loss_sum, g_sum), auxs = lax.scan(
            body, (jax.numpy.float32(0.0), g0), mbs)
        grads = jax.tree_util.tree_map(
            lambda g, s: (g / accum).astype(s.dtype), g_sum, g_shapes)
        loss = loss_sum / accum
        if has_aux:
            return (loss, auxs), grads
        return loss, grads

    return vg_accum


def _uneven_accumulate(vg: Callable, accum: int, has_aux: bool,
                       params, batch, length: int):
    """Row-weighted accumulation over uneven microbatches (the tail of
    ``_accumulate_grads``): unrolled because microbatch shapes differ.
    ``sum_k (rows_k / length) · mean_k`` equals the full-batch mean for
    row-mean losses, so the trajectory matches the divisible case."""
    from jax import lax

    from autodist_tpu.kernel.synchronization.overlap import microbatch_slices

    slices = microbatch_slices(length, accum)
    loss_acc = jax.numpy.float32(0.0)
    g_acc = None
    auxs = []
    for off, rows in slices:
        mb = jax.tree_util.tree_map(
            lambda x: lax.dynamic_slice_in_dim(x, off, rows, 0), batch)
        if has_aux:
            (loss, aux), g = vg(params, mb)
            auxs.append(aux)
        else:
            loss, g = vg(params, mb)
        w = rows / length
        loss_acc = loss_acc + w * loss.astype(jax.numpy.float32)
        if g_acc is None:
            g_acc = jax.tree_util.tree_map(
                lambda x: w * x.astype(jax.numpy.float32), g)
        else:
            g_acc = jax.tree_util.tree_map(
                lambda a, x: a + w * x.astype(jax.numpy.float32), g_acc, g)
    grads = jax.tree_util.tree_map(
        lambda a, x: a.astype(x.dtype), g_acc, g)
    if has_aux:
        aux = jax.tree_util.tree_map(lambda *xs: jax.numpy.stack(xs), *auxs)
        return (loss_acc, aux), grads
    return loss_acc, grads


def _merge_metrics(metrics: Dict, extra: Dict) -> Dict:
    """Merge user metrics, refusing to clobber the framework's keys."""
    overlap = set(metrics) & set(extra)
    if overlap:
        raise ValueError(
            f"metrics_fn returned reserved metric key(s) {sorted(overlap)}; "
            "rename them — 'loss' and 'aux' are produced by the step itself")
    out = dict(metrics)
    out.update(extra)
    return out


def _plan_summary(compiled: CompiledStrategy) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for plan in compiled.var_plans.values():
        key = plan.sync_kind + ("/part" if plan.param_spec != P() else "")
        out[key] = out.get(key, 0) + 1
    return out
