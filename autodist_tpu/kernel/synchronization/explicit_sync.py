"""Explicit (shard_map) synchronization path, bucketed.

The GSPMD path lets XLA insert collectives; this path takes manual control
of the gradient reduction so a :class:`Compressor` can wrap it — the analog
of the reference's AllReduceSynchronizer inserting ``collective_ops.all_reduce``
through a compressor (``all_reduce_synchronizer.py:100-127``,
``compressor.py:85-96``) — and so the sync hot path can be scheduled as
**gradient buckets** instead of one collective per variable.

Semantics: the whole train step runs inside ``shard_map`` over the mesh.
The batch is sharded over ``data``; each device computes local gradients
(accumulated over ``capture(accum_steps=N)`` microbatches of its local slice
when asked — still one compressed collective per bucket per step), and the
gradients synchronize in three tiers:

1. **Buckets** (the default): replicated vars' gradients are flattened
   into size-capped, dtype-grouped contiguous buckets (``bucketing.py``)
   keyed by the strategy's collective group — ONE collective per bucket.
   Compressors quantize per bucket (one scale grid per collective, the
   EQuARX formulation).  Each bucket's chain is data-independent of the
   others, so XLA overlaps one bucket's collective with other buckets'
   compute and with backward work that does not feed it.
2. **ZeRO-1 buckets** (``sync="reduce_scatter"`` plans): the bucket is
   reduce-scattered ((N−1)/N of the all-reduce's reduce bytes), the
   optimizer update runs on the LOCAL 1/N shard of a flat, bucket-major
   optimizer state (the weight-update sharding of arXiv:2004.13336 —
   optimizer HBM drops by the data-axis size), and updated parameters
   are all-gathered back to their replicated layout.  The uneven tail
   bucket is zero-padded to shard evenly; elementwise optimizers
   (SGD/Adam family) make the sharded update exactly equal to the
   replicated one.
3. **Per-variable fallback**: partitioned vars keep their per-shard
   compressed reduction (see below), and non-bucketable compressors
   (PowerSGD needs the 2-D gradient) keep the per-variable collective.

Per-device compressor state (error-feedback residuals, PowerSGD factors)
is carried as a *sync state* pytree with a leading per-shard axis, sharded
over ``data`` so each device owns its slice — bucket-level residuals are
keyed by the bucket id.

With ``capture(numerics=...)`` the **fused numerics guard**
(docs/numerics.md) rides the bucket chain: per-bucket finiteness bits
are a byproduct of the pack, squared-norm partials come from the
reduced values (the reduce-scattered SHARDS under ZeRO-1 — their psum
is exactly the full norm), compressors report pre-quantization wire
saturation, and one small all-axis psum rolls everything into a
``GradHealth`` struct returned with the step metrics.  The same scalars
drive exact global-norm clipping (applied before the local 1/N update),
dynamic loss scaling (state carried under ``"~numerics"`` in the sync
state, checkpointed), and the skip gate (a non-finite step keeps params
and optimizer state bit-identical).

Partitioned variables COMPOSE with compression (the reference can express
PartitionedAR + compressor — ``proto/synchronizers.proto:24-57``): a var
sharded over a non-data mesh axis stays sharded outside the step; inside,
it is all-gathered for the user's loss, its gradient is sliced back to the
local shard, and the data-axis reduction of the SHARD runs through the
compressor — per-shard compressed reduction, each partition reduced
independently, with the parameter + optimizer-state memory of true
partitioning.  Per-variable fallback to replication (with a warning)
covers the cases where the composition is not defined: vars sharded over
``data`` itself, pad-to-divisible vars, multi-axis shardings, and
PowerSGD (its low-rank state is not grad-shaped).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from autodist_tpu.const import MESH_AXIS_DATA
from autodist_tpu.graph_item import GraphItem, path_name
from autodist_tpu.kernel.synchronization import bucketing
from autodist_tpu.kernel.synchronization.bucketing import (
    Bucket,
    MODE_ALL_REDUCE,
    MODE_REDUCE_SCATTER,
    pack_bucket,
    unpack_bucket,
)
from autodist_tpu.kernel.synchronization.compressor import (
    Compressor,
    get_compressor,
)
from autodist_tpu.kernel.synchronization import overlap as overlap_mod
from autodist_tpu.kernel.synchronization import quant_ring
from autodist_tpu.kernel.synchronization import schedule_ir
from autodist_tpu.strategy.compiler import CompiledStrategy
from autodist_tpu.telemetry.timeline import sync_span
from autodist_tpu.utils import logging


def uses_explicit_path(compiled: CompiledStrategy) -> bool:
    """Compressors need manual collectives; fused grouping and explicit
    bucketing need them too (one concat-and-reduce per bucket — the
    reference's scoped-allocator merge done literally); ZeRO-1
    (reduce-scatter weight-update sharding) owns its whole
    reduce→update→gather chain, and an explicit ``overlap=`` schedule
    request needs the schedulable shard_map lowering."""
    for plan in compiled.var_plans.values():
        if plan.compressor not in ("", "NoneCompressor"):
            return True
        if getattr(plan, "sync_mode", "all_reduce") == MODE_REDUCE_SCATTER:
            return True
        if getattr(plan, "bucket_bytes", 0) > 0:
            return True
        if getattr(plan, "overlap", "auto") in (
                overlap_mod.OVERLAP_PIPELINE, overlap_mod.OVERLAP_RING,
                overlap_mod.OVERLAP_FULL):
            return True
        if getattr(plan, "hier", False):
            # two-tier ICI+DCN sync only exists on the shard_map path
            return True
    return (any(plan.fused for plan in compiled.var_plans.values())
            and bool(compiled.fusable_groups()))


def chaos_grad_events_probe():
    """The ``nan_grad``/``inf_grad`` chaos events for this process, or
    [] when none apply / the harness is unavailable — probed so a grad
    injection requested without the numerics guard warns instead of
    silently never firing."""
    try:
        from autodist_tpu.resilience import chaos as chaos_mod
        return chaos_mod.grad_injections()
    except Exception:  # pragma: no cover - chaos env parse errors
        return []


def _compressors_for(gi: GraphItem, compiled: CompiledStrategy
                     ) -> Dict[str, Compressor]:
    out: Dict[str, Compressor] = {}
    for name, leaf in gi.name_to_leaf().items():
        plan = compiled.var_plans.get(name)
        comp_name = plan.compressor if plan else "NoneCompressor"
        out[name] = get_compressor(comp_name or "NoneCompressor")
    return out


def _grad_shaped_state(comp: Compressor, shape: tuple, dtype) -> bool:
    """True when ``comp``'s per-device state for a value of ``shape`` is
    None or a single array of exactly that shape — the structural
    requirement for the per-shard partitioned state layout (one leading
    data axis + the var's own sharding applied to every leaf).  Probed
    abstractly (eval_shape): no state is materialized."""
    probe = jax.eval_shape(comp.init_state,
                           jax.ShapeDtypeStruct(shape, dtype))
    if probe is None:
        return True
    leaves = jax.tree_util.tree_leaves(probe)
    return (len(leaves) == 1 and tuple(leaves[0].shape) == tuple(shape)
            and leaves[0].dtype == dtype)


def partition_drop_reason(spec_axes, shape, dtype, axis_sizes, padded,
                          comp: Compressor) -> Optional[str]:
    """Why the explicit path would drop a partitioned var's sharding, or
    None when the partitioning is kept.

    ``spec_axes`` is the flattened ``[(tensor_dim, mesh_axis_name), ...]``
    of the param layout; ``axis_sizes`` maps axis name → size (a plain
    dict — no mesh needed, so the static analyzer
    (``autodist_tpu.analysis``) shares this exact rule and the lint can
    never drift from the runtime fallback)."""
    spec_axes = list(spec_axes)
    if not spec_axes:
        return None
    if padded:
        return "pad-to-divisible sharding"
    if len(spec_axes) != 1:
        return f"multi-axis sharding {spec_axes}"
    part_axis, axis_name = spec_axes[0]
    if axis_name == MESH_AXIS_DATA:
        return "sharded over the data (reduction) axis"
    n = int(axis_sizes.get(axis_name, 1))
    if n > 1 and shape[part_axis] % n:  # pragma: no cover - padded
        return f"dim {shape[part_axis]} not divisible by {n}"
    shard = list(shape)
    if n > 1:
        shard[part_axis] //= n
    if not _grad_shaped_state(comp, tuple(shard), dtype):
        return (f"{comp.name} state is not grad-shaped"
                f" (e.g. PowerSGD low-rank factors)")
    return None


def _partition_support(gi: GraphItem, compiled: CompiledStrategy,
                       comps: Dict[str, Compressor]) -> Dict[str, tuple]:
    """Which partitioned vars keep their sharding on the explicit path:
    ``{name: (axis_name, part_axis, n_shards)}``.  Unsupported cases
    (see module docstring) are replicated per-variable with a warning."""
    part: Dict[str, tuple] = {}
    pad_names = set(compiled.pad_plans())
    leaves = gi.name_to_leaf()
    axis_sizes = dict(compiled.mesh.shape)
    for name, plan in compiled.var_plans.items():
        spec = plan.param_spec
        if spec == P():
            continue
        spec_axes = []
        for i, e in enumerate(spec):
            if e is None:
                continue
            for a in ([e] if isinstance(e, str) else list(e)):
                spec_axes.append((i, a))
        leaf = jnp.asarray(leaves[name])
        why = partition_drop_reason(spec_axes, leaf.shape, leaf.dtype,
                                    axis_sizes, name in pad_names,
                                    comps[name])
        if why is not None:
            logging.warning(
                "explicit sync path: replicating %s (%s); its "
                "partitioning is dropped for this program", name, why)
            continue
        (part_axis, axis_name), = spec_axes
        part[name] = (axis_name, part_axis, axis_sizes[axis_name])
    return part


def plan_step_buckets(gi: GraphItem, compiled: CompiledStrategy,
                      part: Dict[str, tuple], d: int) -> List[Bucket]:
    """Bucket assignment for this program: every replicated synced var
    whose compressor composes with flat buckets, in flatten order, keyed
    by (mode, dtype, compressor, group).  Shared with the analyzer's
    byte accounting — the planner the runtime executes."""
    entries = []
    cap = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(gi.params)[0]:
        name = path_name(path)
        plan = compiled.var_plans.get(name)
        if plan is None or name in part:
            continue
        comp_name = plan.compressor or "NoneCompressor"
        if bucketing.bucket_drop_reason((), False, comp_name) is not None:
            continue
        mode = getattr(plan, "sync_mode", MODE_ALL_REDUCE) or MODE_ALL_REDUCE
        arr = jnp.asarray(leaf)
        entries.append((name, tuple(arr.shape), str(arr.dtype), comp_name,
                        plan.group, mode))
        cap = max(cap, getattr(plan, "bucket_bytes", 0))
    return bucketing.assign_buckets(
        entries, bucket_bytes=cap or bucketing.DEFAULT_BUCKET_BYTES,
        shard_divisor=max(d, 1))


def make_explicit_step(gi: GraphItem, compiled: CompiledStrategy):
    """Returns (step_fn, init_opt_fn, init_sync_state_fn, param_sh_tree,
    opt_sh_tree, rs_buckets, schedule_ir) consumed by the
    GraphTransformer — ``rs_buckets`` is the planned ZeRO-1 bucket list
    (empty without reduce-scatter plans), exposed so checkpoints can
    record the flat optimizer layout for elastic resume;
    ``schedule_ir`` is the verified sync-schedule program this lowering
    consumed (docs/schedule-ir.md)."""
    import optax

    from autodist_tpu.kernel import sharding_utils as su

    mesh = compiled.mesh
    d = mesh.shape.get(MESH_AXIS_DATA, 1)
    mesh_axis_names = tuple(mesh.axis_names)
    n_devices = 1
    for _a in mesh_axis_names:
        n_devices *= int(mesh.shape[_a])
    comps = _compressors_for(gi, compiled)
    part = _partition_support(gi, compiled, comps)
    name_leaves = {n: jnp.asarray(v) for n, v in gi.name_to_leaf().items()}

    # Effective per-var specs: the plan's spec for supported partitioned
    # vars, replicated for everything else.
    eff_specs = {name: (plan.param_spec if name in part else P())
                 for name, plan in compiled.var_plans.items()}
    param_spec_tree = su.spec_tree_for_params(gi.params, eff_specs)
    param_sh_tree = su.sharding_tree(mesh, param_spec_tree)

    vg = jax.value_and_grad(gi.loss_fn, has_aux=gi.has_aux)
    has_aux = gi.has_aux

    # -- bucket plan -------------------------------------------------------
    buckets = plan_step_buckets(gi, compiled, part, d)
    bucketed_names = {n for b in buckets for n in b.names}
    rs_buckets = [b for b in buckets if b.mode == MODE_REDUCE_SCATTER]
    rs_names = {n for b in rs_buckets for n in b.names}
    # -- hierarchical two-tier sync (docs/schedule-ir.md) ------------------
    # A bucket lowers ICI->DCN->ICI only when EVERY member var's plan
    # opted in AND the data axis factors into >1 slices of >1 devices;
    # the IR builder applies the same gate (plus linear-compressor /
    # no-quantized-wire), so the effective set below is read back from
    # the built IR's bucket nodes — one source of truth.
    num_slices = int(getattr(compiled, "num_slices", 1) or 1)
    hier_on = schedule_ir.hier_applies(d, num_slices)
    hier_keys = [
        b.key for b in buckets
        if hier_on and b.names
        and all(bool(getattr(compiled.var_plans.get(n), "hier", False))
                for n in b.names)]
    for name, plan in compiled.var_plans.items():
        if (getattr(plan, "sync_mode", MODE_ALL_REDUCE)
                == MODE_REDUCE_SCATTER and name not in rs_names):
            logging.warning(
                "explicit sync path: %s requested reduce_scatter (ZeRO-1) "
                "but cannot join a flat bucket (partitioned or "
                "non-bucketable compressor); falling back to its "
                "per-variable/per-shard collective with replicated "
                "optimizer state", name)

    # -- overlap schedule --------------------------------------------------
    # Resolve the step-level overlap plan (``overlap.py``): which buckets
    # pipeline with the microbatch loop, which ring-decompose, and the
    # ZeRO-1 param-gather issue order.  Decisions share one rule set with
    # the analyzer (`sync/overlap-*`) and the cost model.
    ov = overlap_mod.resolve_overlap(
        [getattr(p, "overlap", "auto") or "auto"
         for p in compiled.var_plans.values()],
        accum_steps=gi.accum_steps, buckets=buckets, d=d,
        has_rs=bool(rs_buckets))
    for key, why in ov.drops:
        logging.warning(
            "explicit sync path: overlap scheduling skipped for bucket "
            "%s (%s)", key, why)
    overlap_active = (ov.pipeline or ov.prefetch
                      or ov.mode in (overlap_mod.OVERLAP_PIPELINE,
                                     overlap_mod.OVERLAP_RING,
                                     overlap_mod.OVERLAP_FULL))
    if overlap_active:
        known_names = set(gi.name_to_leaf())
        for name, plan in compiled.var_plans.items():
            if name in bucketed_names or name not in known_names:
                continue
            why = overlap_mod.overlap_drop_reason(
                getattr(plan, "overlap", "auto") or "auto",
                accum_steps=gi.accum_steps,
                compressor=plan.compressor or "NoneCompressor",
                bucketable=False, explicit_path=True)
            if why is not None:
                logging.warning(
                    "explicit sync path: overlap scheduling skipped for "
                    "%s (%s)", name, why)
    # -- numerics guard (docs/numerics.md) ---------------------------------
    # Resolved at build time: loss-scale activation (auto = any
    # low-precision param/bucket dtype), the wire-saturation safety
    # check, and any chaos grad injections (compiled into the step).
    num_cfg = getattr(gi, "numerics", None)
    num_active = bool(num_cfg is not None and num_cfg.guard)
    num_ls = None
    injections: Dict[str, Any] = {}
    if num_active:
        from autodist_tpu.numerics import guard as guard_mod
        from autodist_tpu.numerics import loss_scale as ls_mod

        leaf_dtypes = [str(jnp.asarray(v).dtype)
                       for v in gi.name_to_leaf().values()]
        num_ls = ls_mod.resolve_loss_scale(
            num_cfg.loss_scale,
            leaf_dtypes + [b.dtype for b in buckets])
        for b in buckets:
            why = ls_mod.scale_saturates_wire(num_ls, b.compressor)
            if why is not None:
                raise ValueError(
                    f"numerics: bucket {b.key}: {why}; lower the loss "
                    "scale ceiling or drop the quantizing compressor "
                    "(rule numerics/loss-scale-saturates-wire)")
        injections = guard_mod.resolve_injections(
            buckets, list(gi.name_to_leaf()))
        logging.info(
            "numerics guard: ON (%d buckets, loss_scale=%s, clip_norm=%s, "
            "on_nonfinite=%s)", len(buckets),
            "off" if num_ls is None else
            ("%g dynamic" % num_ls.init if num_ls.dynamic
             else "%g static" % num_ls.init),
            num_cfg.clip_norm, num_cfg.on_nonfinite)
    elif list(chaos_grad_events_probe()):
        logging.warning(
            "AUTODIST_CHAOS requests a gradient injection but the "
            "numerics guard is off — nan_grad/inf_grad need "
            "capture(numerics=...) (the guard owns the device step "
            "counter the injection keys on); ignoring the event")

    def _shard_shape(name: str, leaf) -> tuple:
        shape = list(jnp.asarray(leaf).shape)
        if name in part:
            _, ax, n = part[name]
            shape[ax] //= n
        return tuple(shape)

    # -- sync state --------------------------------------------------------
    # Which vars/buckets carry state and under which spec, probed
    # abstractly ONCE (eval_shape — no full-model state is materialized
    # just to test for None); consumed by the schedule IR below, the
    # shard_map specs, and init_sync_state.  Bucket-level residuals are
    # keyed by bucket id (per-bucket error feedback — the EQuARX
    # composition); per-variable state remains only for partitioned and
    # non-bucketable vars.
    sync_specs: Dict[str, P] = {}
    sync_builders: Dict[str, Any] = {}
    for name, leaf in name_leaves.items():
        if name in bucketed_names or name not in comps:
            continue
        if compiled.var_plans.get(name) is None and name not in part:
            continue
        probe = jax.eval_shape(
            comps[name].init_state,
            jax.ShapeDtypeStruct(_shard_shape(name, leaf), leaf.dtype))
        if probe is None:
            continue
        sync_specs[name] = P(MESH_AXIS_DATA,
                             *compiled.var_plans[name].param_spec) \
            if name in part else P(MESH_AXIS_DATA)
        sync_builders[name] = ("var", name)
    for b in buckets:
        comp = get_compressor(b.compressor)
        probe = jax.eval_shape(
            comp.init_state,
            jax.ShapeDtypeStruct((b.padded_total,), jnp.dtype(b.dtype)))
        if probe is None:
            continue
        sync_specs[b.key] = P(MESH_AXIS_DATA)
        sync_builders[b.key] = ("bucket", b)
    if num_active:
        # Numerics state (loss scale + health counters): replicated
        # scalars carried in the step like optimizer state — and
        # checkpointed with the sync state, so resume keeps the scale.
        from autodist_tpu.numerics.guard import NUMERICS_KEY
        sync_specs[NUMERICS_KEY] = P()
        sync_builders[NUMERICS_KEY] = ("numerics", None)
    # Donation audit: params and optimizer state are rewritten every step,
    # so donating them is always safe.  Sync state is donated ONLY when
    # every entry is a bucket residual (rewritten unconditionally by the
    # bucket compressor each step).  Per-variable fallback entries
    # (partitioned / PowerSGD tier) can pass through a step untouched —
    # e.g. a compressor that returns its state unchanged — and returning
    # a donated input aliases a buffer whose old handle (held by a
    # checkpoint saver or a caller inspecting ``session.sync_state``
    # across steps) is now marked deleted.  Fallback programs keep their
    # sync state undonated; its footprint is small (residual tensors for
    # the handful of vars the buckets could not absorb).
    # (Numerics state is rewritten unconditionally every step, so it is
    # donation-safe like bucket residuals.)  The schedule verifier
    # re-proves this as the schedule/read-after-donate rule.
    donate_sync = all(kind in ("bucket", "numerics")
                      for kind, _ in sync_builders.values())

    # -- fused Pallas kernels (docs/kernels.md) ----------------------------
    # Opt-in via AUTODIST_FUSED_KERNELS; a requested kernel this
    # program cannot lower raises on a TPU and, off-TPU, falls back to
    # the unfused path, either way with the SHARED drop-reason string
    # (ops.fused_kernels.fused_drop_reason — the analysis schedule pass
    # surfaces the same rule).  The active
    # set is recorded in the schedule IR below, so the fingerprint, the
    # verifier, and the cost model all see the fused program.
    from autodist_tpu.ops import fused_kernels as fk

    opt_fusable = getattr(gi.optimizer, "fused_spec", None) is not None
    adam_shaped = True
    if opt_fusable and rs_buckets:
        opt_probe = jax.eval_shape(
            gi.optimizer.init,
            {"x": jax.ShapeDtypeStruct((8,), jnp.float32)})
        adam_shaped = fk.find_adam_state(opt_probe) is not None
    active_fused, fused_drops = fk.resolve_fused(
        guard=num_active, has_rs=bool(rs_buckets),
        has_quant_ring=any(quant_ring.wire_format_of(b.compressor)
                           is not None for b in buckets),
        optimizer_fusable=opt_fusable, adam_state_shaped=adam_shaped,
        f32_buckets=all(b.dtype == "float32" for b in rs_buckets))
    for kernel, why in fused_drops:
        fk.drop_or_raise("explicit sync path", kernel, why)
    # Interpret-mode decision resolved HERE, at build — not at trace —
    # the ops/flash_attention.py convention (off-TPU is only reachable
    # under the AUTODIST_FUSED_INTERPRET escape hatch).
    fused_interpret = not fk.kernels_runnable()[0]
    guard_fused = fk.KERNEL_GUARD in active_fused
    update_fused = fk.KERNEL_UPDATE in active_fused
    if active_fused:
        logging.info("explicit sync path: fused Pallas kernels active: "
                     "%s%s", ",".join(active_fused),
                     " (interpret mode)" if fused_interpret else "")

    # -- schedule IR (docs/schedule-ir.md) ---------------------------------
    # The sync program as a first-class artifact: one IR instance built
    # from the planner + overlap + guard + donation facts above; this
    # lowering CONSUMES it (pipeline membership, per-bucket reduce
    # algorithm, ZeRO-1 gather issue order), and the static verifier
    # model-checks it before anything traces.  The same instance rides
    # the DistributedStep for telemetry fingerprints and checkpoints.
    per_var_entries = []
    for name, plan in compiled.var_plans.items():
        if name in bucketed_names or name not in name_leaves:
            continue
        vi = gi.info.by_name(name)
        if vi is None:
            continue
        leaf = name_leaves[name]
        per_var_entries.append(schedule_ir.PerVarEntry(
            name=name, dtype=str(leaf.dtype),
            nbytes=int(leaf.size) * leaf.dtype.itemsize,
            sync_kind="AllReduce",
            compressor=plan.compressor or "NoneCompressor",
            sig=schedule_ir.fact_from_varplan(plan, vi).sig(),
            stateful=name in sync_builders))
    ir_axes = {str(a): int(mesh.shape[a]) for a in mesh_axis_names}
    ir = schedule_ir.build_schedule_ir(
        axes=ir_axes,
        accum_steps=gi.accum_steps, buckets=buckets, plan=ov,
        num_slices=num_slices, hier_keys=hier_keys,
        per_var=per_var_entries, guard=num_active,
        donated=tuple(f"sync:{k}" for k in sync_builders) if donate_sync
        else (),
        stateful_keys={k for k, (kind, _) in sync_builders.items()
                       if kind == "bucket"},
        fused_kernels=active_fused,
        # MoE expert a2as (docs/schedule-ir.md): derived from the SAME
        # expert-flagged catalog the analyzer sees, so both sides carry
        # identical dispatch/combine legs and fingerprints.
        moe=schedule_ir.moe_facts_from_vars(gi.info.variables,
                                            axes=ir_axes))
    schedule_ir.assert_verified(ir, "explicit sync build")
    logging.info(
        "explicit sync path: schedule IR %s (%d bucket(s), %d leg(s), "
        "overlap=%s)", ir.fingerprint(), len(ir.buckets), len(ir.legs),
        ir.overlap_mode)

    pipe_keys = ir.pipelined_keys()
    pipe_buckets = [b for b in buckets if b.key in pipe_keys]

    # -- flight-recorder leg stamps (docs/observability.md) ----------------
    # Under AUTODIST_FLIGHTREC=legs (the automatic choice on TPU) the
    # step stamps a host-callback cursor at every leg GROUP boundary —
    # per-bucket reduce, ZeRO-1 update, per-bucket param gather, guard
    # rollup — keyed by the IR's own leg ids, so a wedge localizes to
    # the exact leg the happens-before relation knows.  Resolved at
    # build: the default off-TPU path compiles no callbacks at all.
    from autodist_tpu.telemetry import flightrec

    leg_stamps = flightrec.trace_stamps_enabled()
    stamp_reduce: Dict[str, tuple] = {}   # key -> (leg id/template, kind)
    stamp_gather: Dict[str, tuple] = {}
    stamp_update: Dict[str, tuple] = {}
    if leg_stamps:
        import re as _re
        for b in buckets:
            finals = [l for l in ir.legs
                      if l.bucket == b.key and f"red:{b.key}" in l.writes]
            if not finals:
                continue
            if b.key in pipe_keys:
                # Per-slot ids ("<key>@<slot>/..."): a {slot} template
                # the callback resolves with the live microbatch index.
                stamp_reduce[b.key] = (
                    _re.sub(r"@\d+/", "@{slot}/", finals[0].id),
                    finals[0].kind)
            else:
                stamp_reduce[b.key] = (finals[-1].id, finals[-1].kind)
        for l in ir.legs:
            if l.id.startswith("update/"):
                stamp_update[l.bucket] = (l.id, l.kind)
        for b in rs_buckets:
            finals = [l for l in ir.legs
                      if l.bucket == b.key and "@gather" in l.id
                      and f"param:{b.key}" in l.writes]
            if finals:
                stamp_gather[b.key] = (finals[-1].id, finals[-1].kind)
    # Mean-reduction lowering per UNCOMPRESSED bucket under the IR's
    # resolved algorithm (ring / one-shot / XLA fused); compressed
    # buckets keep their compressor's own wire format.
    # Effective hier set: read back from the built IR's bucket nodes so
    # the runtime closures and the verified program can never disagree
    # about which buckets went two-tier.
    hier_bucket_keys = {n["key"] for n in ir.buckets if n.get("hier")}
    hier_dcn_fmt = quant_ring.wire_format_of(
        schedule_ir.dcn_wire_compressor_default())
    reduce_fns = {b.key: (
        overlap_mod.hier_bucket_reduce_fn(
            b, MESH_AXIS_DATA, d, num_slices, dcn_wire=hier_dcn_fmt)
        if b.key in hier_bucket_keys else
        overlap_mod.bucket_reduce_fn(
            b, ov, MESH_AXIS_DATA, d, alg=ir.reduce_alg(b.key)))
        for b in buckets
        if overlap_mod.is_linear_compressor(b.compressor)}
    # Quantized-wire buckets (int8/fp8, docs/overlap.md) lower through
    # the stateful bucket entry point under the IR-resolved algorithm:
    # (vec, error-feedback state) -> (reduced, new state, saturation
    # count).  The same closures serve the end-of-step tier and the
    # per-microbatch-slot pipeline.
    quant_fns = {}
    for b in buckets:
        if quant_ring.wire_format_of(b.compressor) is None:
            continue
        comp = get_compressor(b.compressor)
        node = ir.bucket_node(b.key) or {}
        hop_fused = bool(node.get("hop_fused", False))
        if b.mode == MODE_REDUCE_SCATTER:
            quant_fns[b.key] = (
                lambda v, s, comp=comp, alg=ir.reduce_alg(b.key),
                hf=hop_fused:
                comp.bucket_reduce_scatter(v, s, MESH_AXIS_DATA, d,
                                           alg=alg, hop_fused=hf))
        else:
            quant_fns[b.key] = (
                lambda v, s, comp=comp, alg=ir.reduce_alg(b.key),
                hf=hop_fused:
                comp.bucket_reduce(v, s, MESH_AXIS_DATA, d, alg=alg,
                                   hop_fused=hf))
    pipe_quant_fns = {k: f for k, f in quant_fns.items() if k in pipe_keys}
    # Saturation counters are per-data-rank events replicated across the
    # other mesh axes; this factor makes the guard's all-axis psum
    # return the true global count.
    sat_norm = d / float(n_devices)
    reduced_sizes = {b.key: (b.padded_total // max(d, 1)
                             if b.mode == MODE_REDUCE_SCATTER
                             else b.padded_total) for b in buckets}
    use_pipeline = bool(pipe_buckets) and gi.accum_steps > 1
    if gi.accum_steps > 1 and not use_pipeline and not num_active:
        # Gradient accumulation composes with compression exactly where it
        # matters most (bandwidth-starved links): the f32 accumulator scan
        # runs INSIDE the shard_map step over the device's LOCAL microbatch
        # slices, so each bucket still sees ONE averaged gradient — one
        # compressed collective per bucket per step, N microbatches of
        # activations.  (With the numerics guard the wrap happens inside
        # local_step instead — the loss scale and chaos injections bind
        # to per-step state first.)
        from autodist_tpu.kernel.graph_transformer import _accumulate_grads
        vg = _accumulate_grads(vg, gi.accum_steps, gi.has_aux)

    if num_ls is not None:
        # Loss scaling: the loss is multiplied by the (power-of-two)
        # scale BEFORE the backward pass so small gradients survive a
        # low-precision exponent range; reduced gradients are divided by
        # it before clipping and the update.  Built as a 3-arg
        # value-and-grad so the scale can come from the step's state.
        def _scaled_loss(p, batch, scale):
            if has_aux:
                loss_, aux_ = gi.loss_fn(p, batch)
                return loss_ * scale, aux_
            return gi.loss_fn(p, batch) * scale
        vg_scaled = jax.value_and_grad(_scaled_loss, has_aux=has_aux)
    else:
        vg_scaled = None

    # -- optimizer split ---------------------------------------------------
    # ZeRO-1 vars' optimizer state lives as flat bucket-major shards (one
    # leaf per bucket, sharded over 'data'); everything else keeps the
    # tree-shaped state.  The tree optimizer masks ZeRO-1 vars (and frozen
    # vars) to zero updates / no state — the 1/N state memory win.
    if rs_buckets:
        frozen = {v.name for v in gi.info.untrainable_variables}

        def label_of(path, _):
            name = path_name(path)
            return "zero" if (name in rs_names or name in frozen) \
                else "train"
        labels = jax.tree_util.tree_map_with_path(label_of, gi.params)
        tree_optimizer = optax.multi_transform(
            {"train": gi.optimizer, "zero": optax.set_to_zero()}, labels)
        bucket_optimizer = gi.optimizer
    else:
        tree_optimizer = gi.frozen_aware_optimizer()
        bucket_optimizer = None

    # Optimizer-state layout: param-shaped blocks follow the effective
    # param spec (shard-local moments for partitioned vars — the real
    # memory win of keeping the partitioning); scalars replicate.  ZeRO-1
    # bucket shards ride a parallel {"zero1": ...} subtree sharded flat
    # over 'data' (each device owns 1/d of every bucket's moments).
    tree_opt_shape = jax.eval_shape(tree_optimizer.init, gi.params)
    tree_opt_spec = su.opt_spec_tree(tree_opt_shape, gi.params,
                                     param_spec_tree)

    def _bucket_template():
        return {b.key: jax.ShapeDtypeStruct((b.padded_total,),
                                            jnp.dtype(b.dtype))
                for b in rs_buckets}

    def _pack_params_vecs(params):
        by_name = {path_name(p): x for p, x in
                   jax.tree_util.tree_flatten_with_path(params)[0]}
        return {b.key: pack_bucket(b, [by_name[n] for n in b.names])
                for b in rs_buckets}

    if rs_buckets:
        template = _bucket_template()
        z_shape = jax.eval_shape(bucket_optimizer.init, template)
        z_spec = su.opt_spec_tree(
            z_shape, template, {b.key: P(MESH_AXIS_DATA)
                                for b in rs_buckets})
        opt_spec_tree = {"vars": tree_opt_spec, "zero1": z_spec}

        def init_opt(params):
            return {"vars": tree_optimizer.init(params),
                    "zero1": bucket_optimizer.init(_pack_params_vecs(params))}
    else:
        opt_spec_tree = tree_opt_spec
        init_opt = tree_optimizer.init
    opt_sh_tree = su.sharding_tree(mesh, opt_spec_tree)

    def init_sync_state(current_params=None):
        # Compressor residuals start at zero regardless of parameter values,
        # so current_params only matters for shape (identical to capture-time).
        state: Dict[str, Any] = {}
        for key, (kind, ref) in sync_builders.items():
            spec = sync_specs[key]
            if kind == "numerics":
                from autodist_tpu.numerics import loss_scale as ls_mod
                state[key] = jax.device_put(
                    ls_mod.init_state(num_ls), NamedSharding(mesh, spec))
                continue
            if kind == "bucket":
                b = ref
                per_dev = get_compressor(b.compressor).init_state(
                    jnp.zeros((b.padded_total,), jnp.dtype(b.dtype)))
                stacked = jax.tree_util.tree_map(
                    lambda s: jnp.broadcast_to(s[None],
                                               (d,) + s.shape).copy(),
                    per_dev)
                state[key] = jax.device_put(
                    stacked, NamedSharding(mesh, spec))
                continue
            name = ref
            leaf = name_leaves[name]
            if name in part:
                # Partitioned state is built THROUGH the compressor's own
                # init_state on a shard-shaped zero input (the gate and
                # the construction cannot diverge), tiled to (d,) + FULL
                # shape directly in its target sharding — each device
                # owns its shard's state.
                _, ax, n = part[name]
                shard = _shard_shape(name, leaf)

                def _build(comp=comps[name], shard=shard, dt=leaf.dtype,
                           ax=ax, n=n):
                    def expand(s):
                        reps = [n if i == ax else 1
                                for i in range(s.ndim)]
                        tiled = jnp.tile(s, reps)
                        return jnp.broadcast_to(tiled[None],
                                                (d,) + tiled.shape)
                    return jax.tree_util.tree_map(
                        expand, comp.init_state(jnp.zeros(shard, dt)))

                state[name] = jax.jit(
                    _build, out_shardings=NamedSharding(mesh, spec))()
            else:
                per_dev = comps[name].init_state(leaf)
                stacked = jax.tree_util.tree_map(
                    lambda s: jnp.broadcast_to(s[None],
                                               (d,) + s.shape).copy(),
                    per_dev)
                state[name] = jax.device_put(
                    stacked, NamedSharding(mesh, spec))
        return state

    # -- the local (per-shard) step ---------------------------------------
    def local_step(params, opt_state, sync_state, batch):
        params_in, opt_in = params, opt_state
        # Reconstruct full tensors for the user's loss: sharded vars are
        # all-gathered over their partition axis (what GSPMD inserts for
        # a fully-consumed sharded param; here it is explicit).
        flat_p, ptree = jax.tree_util.tree_flatten_with_path(params)
        full_leaves = []
        for path, x in flat_p:
            info = part.get(path_name(path))
            if info is not None:
                axis_name, ax, _ = info
                x = lax.all_gather(x, axis_name, axis=ax, tiled=True)
            full_leaves.append(x)
        full_params = jax.tree_util.tree_unflatten(ptree, full_leaves)

        # Numerics guard: bind this step's loss scale / device step
        # counter, then assemble the value-and-grad the tiers below run
        # (scale → chaos injection → accumulation, innermost first).
        if num_active:
            ns = sync_state[NUMERICS_KEY]
            scale = ns["scale"] if num_ls is not None else None
            health = guard_mod.HealthAccumulator(
                n_devices, fused=guard_fused,
                interpret=fused_interpret if guard_fused else None)
            if scale is None:
                vg_local = vg
            else:
                vg_local = lambda p, b: vg_scaled(p, b, scale)  # noqa: E731
            if injections:
                vg_local = guard_mod.wrap_injections(
                    vg_local, injections, ns["step"])
            if gi.accum_steps > 1 and not use_pipeline:
                from autodist_tpu.kernel.graph_transformer import \
                    _accumulate_grads
                vg_local = _accumulate_grads(vg_local, gi.accum_steps,
                                             has_aux)
        else:
            scale = None
            vg_local = vg
        guarded_idx: List[int] = []

        pipe_reduced: Dict[str, Any] = {}
        pipe_qstates: Dict[str, Any] = {}
        pipe_qsats: Dict[str, Any] = {}
        if use_pipeline:
            # Accumulation pipelining (overlap.py): microbatch k's bucket
            # collectives are issued alongside microbatch k+1's backward;
            # only the last microbatch's reduction is exposed.  `grads`
            # carries the locally averaged tree for the per-variable and
            # non-pipelined compressed-bucket tiers, whose single
            # end-of-step collective is unchanged.  Quantized pipelined
            # buckets issue one quantized collective per slot with their
            # error-feedback residual threaded through the loop.
            def single_vg(p, mb):
                if has_aux:
                    (loss_, aux_), g_ = vg_local(p, mb)
                else:
                    loss_, g_ = vg_local(p, mb)
                    aux_ = None
                return loss_, aux_, g_

            qstates0 = {
                k: jax.tree_util.tree_map(lambda x: jnp.squeeze(x, 0),
                                          sync_state[k])
                for k in pipe_quant_fns if k in sync_state}
            (loss, aux, grads, pipe_reduced, pipe_qstates,
             pipe_qsats) = overlap_mod.pipelined_accumulate(
                single_vg, gi.accum_steps, has_aux, pipe_buckets,
                reduce_fns, reduced_sizes, full_params, batch,
                quant_fns=pipe_quant_fns, quant_states=qstates0,
                stamps={k: v for k, v in stamp_reduce.items()
                        if k in pipe_keys} if leg_stamps else None)
        elif has_aux:
            (loss, aux), grads = vg_local(full_params, batch)
        else:
            loss, grads = vg_local(full_params, batch)
            aux = None

        flat, treedef = jax.tree_util.tree_flatten_with_path(grads)
        idx_of = {path_name(path): i for i, (path, _) in enumerate(flat)}
        new_sync = dict(sync_state)
        synced = [g for _, g in flat]   # pass-through default (frozen vars)

        def local_state_of(key):
            st = sync_state.get(key)
            return None if st is None else jax.tree_util.tree_map(
                lambda x: jnp.squeeze(x, 0), st)

        def store_state(key, st2):
            if st2 is not None and key in new_sync:
                new_sync[key] = jax.tree_util.tree_map(
                    lambda x: jnp.expand_dims(x, 0), st2)

        # Tier 3: per-variable fallbacks — partitioned per-shard reduction
        # and non-bucketable compressors (PowerSGD).
        for i, (path, g) in enumerate(flat):
            name = path_name(path)
            if name in bucketed_names or compiled.var_plans.get(name) is None:
                continue
            info = part.get(name)
            if info is not None:
                # Per-shard compressed reduction: slice this device's
                # shard of the full gradient, then compress its data-axis
                # mean.  Slicing commutes with the mean, so the result is
                # exact; only the shard crosses the compressed wire.
                axis_name, ax, n = info
                size = g.shape[ax] // n
                idx = lax.axis_index(axis_name)
                g = lax.dynamic_slice_in_dim(g, idx * size, size, ax)
            with sync_span(f"per_var_reduce/{name}"):
                g2, st2 = comps[name].reduce(g, local_state_of(name),
                                             MESH_AXIS_DATA)
            store_state(name, st2)
            synced[i] = g2
            guarded_idx.append(i)
            if num_active:
                # Finiteness from the PRE-compress local gradient (the
                # injected/overflowed value a lossy compressor could
                # mask); the norm partial from the reduced value the
                # update will consume.  Partitioned shards psum over
                # their model axis too, so nothing is double counted.
                health.add(
                    name, g2,
                    shard_axes_size=part[name][2] if info is not None else 1,
                    finite_src=g,
                    saturation=guard_mod.wire_saturation(
                        g, ls_mod.wire_dtype_of(comps[name].name)))

        # Tiers 1+2: one collective per bucket.  Each bucket's chain
        # (pack → collective [→ shard update → all-gather]) depends only
        # on its own members' gradients, so XLA's scheduler is free to
        # overlap bucket collectives with other buckets' math and with
        # backward compute that does not feed them.  Pipelined buckets
        # arrive already reduced (per microbatch, see above); uncompressed
        # buckets reduce through the overlap schedule's lowering (ring
        # decomposition / one-shot / XLA fused collective).
        rs_grad_shards: Dict[str, Any] = {}
        for b in buckets:
            rs = b.mode == MODE_REDUCE_SCATTER
            if b.key in pipe_keys:
                red = pipe_reduced[b.key]
                if num_active:
                    # Linear pipelined buckets: a NaN survives the linear
                    # per-microbatch reduction — the accumulated reduced
                    # value IS the finiteness source.  Quantized
                    # pipelined buckets additionally report the
                    # saturation events counted inside their ring legs
                    # (a quantizer can mask a NaN on the wire; the
                    # counter cannot).
                    health.add(b.key, red, shard_axes_size=d if rs else 1,
                               sat_count=pipe_qsats[b.key] * sat_norm
                               if b.key in pipe_qsats else None)
                if b.mode == MODE_ALL_REDUCE:
                    for n, arr in zip(b.names, unpack_bucket(b, red)):
                        synced[idx_of[n]] = arr
                        guarded_idx.append(idx_of[n])
                else:
                    rs_grad_shards[b.key] = red
                store_state(b.key, pipe_qstates.get(b.key))
                continue
            if b.key in stamp_reduce:   # flight-recorder leg boundary
                lid, lkind = stamp_reduce[b.key]
                flightrec.traced_stamp(lid, leg_kind=lkind)
            vec = pack_bucket(b, [flat[idx_of[n]][1] for n in b.names])
            if b.key in reduce_fns:   # uncompressed: schedule-lowered
                # Profiler attribution (docs/observability.md): the
                # named scope prefixes this bucket's lowered collective
                # ops, so a trace shows reduce-scatter vs all-gather vs
                # update time per bucket by name.
                with sync_span(f"bucket_reduce/{b.key}"):
                    red = reduce_fns[b.key](vec)
                st2 = None
                if num_active:
                    # The per-bucket finiteness bit is a byproduct of the
                    # pack (the local packed vector); the norm partial
                    # comes from the reduced value — the scattered SHARD
                    # for ZeRO-1 buckets, whose shard sq-norms psum to
                    # exactly the full bucket norm.
                    health.add(b.key, red, shard_axes_size=d if rs else 1,
                               finite_src=vec)
                if b.mode == MODE_ALL_REDUCE:
                    for n, arr in zip(b.names, unpack_bucket(b, red)):
                        synced[idx_of[n]] = arr
                        guarded_idx.append(idx_of[n])
                else:
                    rs_grad_shards[b.key] = red
            elif b.key in quant_fns:
                # Quantized wire (int8/fp8): the bucket lowers through
                # quant_ring under the IR-resolved algorithm (per-hop
                # requantizing ring or one-shot all_to_all), and the
                # post-quantization saturation counter — clipped-to-rail
                # / fp8-overflow elements, counted INSIDE the legs —
                # rides the health rollup.
                with sync_span(f"bucket_quant_reduce/{b.key}"):
                    red, st2, qsat = quant_fns[b.key](
                        vec, local_state_of(b.key))
                if b.mode == MODE_ALL_REDUCE:
                    if num_active:
                        health.add(b.key, red, shard_axes_size=1,
                                   finite_src=vec,
                                   sat_count=qsat * sat_norm)
                    for n, arr in zip(b.names, unpack_bucket(b, red)):
                        synced[idx_of[n]] = arr
                        guarded_idx.append(idx_of[n])
                else:
                    rs_grad_shards[b.key] = red
                    if num_active:
                        health.add(b.key, red, shard_axes_size=d,
                                   finite_src=vec,
                                   sat_count=qsat * sat_norm)
            else:
                comp = get_compressor(b.compressor)
                sat = guard_mod.wire_saturation(
                    vec, ls_mod.wire_dtype_of(b.compressor)) \
                    if num_active else None
                if b.mode == MODE_ALL_REDUCE:
                    with sync_span(f"bucket_compressed_reduce/{b.key}"):
                        red, st2 = comp.reduce(vec, local_state_of(b.key),
                                               MESH_AXIS_DATA)
                    if num_active:
                        health.add(b.key, red, shard_axes_size=1,
                                   finite_src=vec, saturation=sat)
                    for n, arr in zip(b.names, unpack_bucket(b, red)):
                        synced[idx_of[n]] = arr
                        guarded_idx.append(idx_of[n])
                else:
                    with sync_span(f"bucket_compressed_reduce/{b.key}"):
                        rs_grad_shards[b.key], st2 = comp.reduce_scatter(
                            vec, local_state_of(b.key), MESH_AXIS_DATA)
                    if num_active:
                        health.add(b.key, rs_grad_shards[b.key],
                                   shard_axes_size=d, finite_src=vec,
                                   saturation=sat)
            store_state(b.key, st2)

        # -- fused guard roll-up: ONE psum combines every bucket/var
        # partial; unscale + global-norm clip multiply into the synced
        # gradients before any update (exact under ZeRO-1: the factor is
        # computed from the psum of shard norms, identical on every
        # device).
        all_finite = gnorm = per_bucket = new_ns = None
        fused_mult = None
        if num_active:
            inv_scale = jnp.float32(1.0) if scale is None \
                else jnp.float32(1.0) / scale
            if leg_stamps:
                flightrec.traced_stamp("guard/rollup",
                                       leg_kind=schedule_ir.LEG_PSUM_GUARD)
            with sync_span("guard_rollup"):
                all_finite, gnorm, per_bucket = health.finalize(
                    mesh_axis_names, loss, inv_scale)
            mult = inv_scale
            clip = guard_mod.clip_multiplier(gnorm, num_cfg.clip_norm)
            if clip is not None:
                mult = mult * clip
            if clip is not None or scale is not None:
                for i in set(guarded_idx):
                    g_i = synced[i]
                    synced[i] = (g_i.astype(jnp.float32)
                                 * mult).astype(g_i.dtype)
                if update_fused:
                    # The fused unscale/clip/update kernel folds the
                    # multiplier into the shard update itself — the
                    # gradient shards stay untouched here (one fewer
                    # full pass over every ZeRO-1 bucket).
                    fused_mult = mult
                else:
                    rs_grad_shards = {
                        k: (v.astype(jnp.float32) * mult).astype(v.dtype)
                        for k, v in rs_grad_shards.items()}
        grads = jax.tree_util.tree_unflatten(treedef, synced)

        # Shard-local update: grads, params, and opt state all carry the
        # per-device shard shapes, so elementwise optimizers (SGD, Adam*)
        # update each partition in place.  (Global-norm clipping — the
        # one cross-parameter coupling that matters — is handled by the
        # numerics guard above, whose psum'd norm makes the sharded clip
        # exact; other coupled optimizers still need the GSPMD path.)
        if rs_buckets:
            # ZeRO-1: update the local 1/d shard of every reduce-scattered
            # bucket, then all-gather fresh parameters ("broadcast from
            # the PS" in reference terms).  Params are replicated inside
            # the step, so slicing this device's shard is local.
            shard_idx = lax.axis_index(MESH_AXIS_DATA)
            by_name = {path_name(p): x for p, x in flat_p}
            p_shards = {}
            for b in rs_buckets:
                vec = pack_bucket(b, [by_name[n] for n in b.names])
                sz = b.padded_total // d
                if b.key in hier_bucket_keys:
                    # Two-tier scatter permutes ownership: device
                    # g*d_in+i ends with global chunk i*s+g, so slice
                    # the matching param chunk for the shard update.
                    d_in = d // num_slices
                    owner = ((shard_idx % d_in) * num_slices
                             + shard_idx // d_in)
                    p_shards[b.key] = lax.dynamic_slice_in_dim(
                        vec, owner * sz, sz, 0)
                else:
                    p_shards[b.key] = lax.dynamic_slice_in_dim(
                        vec, shard_idx * sz, sz, 0)
            if rs_buckets and rs_buckets[0].key in stamp_update:
                lid, lkind = stamp_update[rs_buckets[0].key]
                flightrec.traced_stamp(lid, leg_kind=lkind)
            if update_fused:
                # Fused unscale/clip/Adam update (docs/kernels.md): one
                # kernel per bucket shard over (p, g, m, v) — exact vs
                # the optax chain (fusable_adam pins the hyperparams);
                # the shared step counter increments once, like optax.
                spec = gi.optimizer.fused_spec
                with sync_span("fused_shard_update"):
                    adam_st = fk.find_adam_state(opt_state["zero1"])
                    new_shards, new_mu, new_nu = {}, {}, {}
                    for b in rs_buckets:
                        key = b.key
                        (new_shards[key], new_mu[key],
                         new_nu[key]) = fk.fused_adam_update(
                            p_shards[key], rs_grad_shards[key],
                            adam_st.mu[key], adam_st.nu[key],
                            adam_st.count, spec, mult=fused_mult,
                            interpret=fused_interpret)
                    z_state = fk.replace_adam_state(
                        opt_state["zero1"],
                        adam_st._replace(count=adam_st.count + 1,
                                         mu=new_mu, nu=new_nu))
            else:
                with sync_span("zero1_shard_update"):
                    z_updates, z_state = bucket_optimizer.update(
                        rs_grad_shards, opt_state["zero1"], p_shards)
                    new_shards = optax.apply_updates(p_shards, z_updates)

            with sync_span("tree_update"):
                t_updates, t_state = tree_optimizer.update(
                    grads, opt_state["vars"], params)
                params = optax.apply_updates(params, t_updates)

            new_flat = [x for _, x in
                        jax.tree_util.tree_flatten_with_path(params)[0]]
            # Param prefetch: gathers issue in the IR's recorded order —
            # reverse bucket order under prefetch (the last bucket's
            # shard update completes first under the backward-interleaved
            # schedule), and large buckets ring-decompose the gather so
            # its legs interleave with the remaining shard updates.
            rs_by_key = {b.key: b for b in rs_buckets}
            for key, gather_alg in ir.gather_plan():
                b = rs_by_key[key]
                shard = new_shards[b.key]
                if key in stamp_gather:   # flight-recorder leg boundary
                    lid, lkind = stamp_gather[key]
                    flightrec.traced_stamp(lid, leg_kind=lkind)
                with sync_span(f"param_gather/{b.key}"):
                    if key in hier_bucket_keys:
                        # DCN gather (across slices, chunk order) then
                        # ICI gather (within slice) undoes the two-tier
                        # ownership permutation exactly.
                        full_vec = overlap_mod.hier_gather_fn(
                            MESH_AXIS_DATA, d, num_slices)(shard)
                    elif gather_alg == schedule_ir.ALG_RING and d > 1:
                        full_vec = overlap_mod.ring_all_gather(
                            shard, MESH_AXIS_DATA, d)
                    else:
                        full_vec = lax.all_gather(shard, MESH_AXIS_DATA,
                                                  axis=0, tiled=True)
                for n, arr in zip(b.names, unpack_bucket(b, full_vec)):
                    new_flat[idx_of[n]] = arr
            params = jax.tree_util.tree_unflatten(treedef, new_flat)
            opt_state = {"vars": t_state, "zero1": z_state}
        else:
            if "~tree" in stamp_update:
                lid, lkind = stamp_update["~tree"]
                flightrec.traced_stamp(lid, leg_kind=lkind)
            with sync_span("tree_update"):
                updates, opt_state = tree_optimizer.update(grads, opt_state,
                                                           params)
                params = optax.apply_updates(params, updates)
        mean_loss = lax.pmean(loss, MESH_AXIS_DATA)
        metrics = {"loss": mean_loss}
        if num_active:
            # Skip gate: a non-finite step keeps params AND optimizer
            # state bit-identical (zero-update), backs the loss scale
            # off, and counts the skip — the step policy's device half.
            params = guard_mod.tree_select(all_finite, params, params_in)
            opt_state = guard_mod.tree_select(all_finite, opt_state, opt_in)
            # Compressor state (error-feedback residuals, PowerSGD
            # factors) must roll back too: a skipped step's poisoned
            # residual would otherwise re-contaminate every later step.
            for key in list(new_sync):
                if key != NUMERICS_KEY and key in sync_state:
                    new_sync[key] = guard_mod.tree_select(
                        all_finite, new_sync[key], sync_state[key])
            new_ns = ls_mod.update_state(ns, all_finite, num_ls)
            new_sync[NUMERICS_KEY] = new_ns
            if scale is not None:
                metrics["loss"] = mean_loss * inv_scale
            metrics["grad_health"] = guard_mod.GradHealth(
                all_finite=all_finite, global_norm=gnorm,
                loss_scale=ns["scale"], skipped_steps=new_ns["skipped"],
                per_bucket=per_bucket)
        if aux is not None:
            metrics["aux"] = jax.tree_util.tree_map(
                lambda x: lax.pmean(x, MESH_AXIS_DATA), aux)
        # extra metrics_fn runs OUTSIDE this shard_map (graph_transformer
        # wraps the step) so it sees the global batch, not a local shard.
        return params, opt_state, new_sync, metrics

    # check_vma=False: this path OWNS its collectives.  With vma tracking on
    # (the jax 0.9 default), replicated (P()) params get pvary'd on entry and
    # the loss's backward transpose AUTO-INSERTS a psum per variable — the
    # gradients would arrive pre-summed and the compressor pmean would then
    # scale them by the data-axis size (d x too large), while the real
    # collective escapes the compressor entirely.
    mapped = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(param_spec_tree, opt_spec_tree, dict(sync_specs),
                  P(MESH_AXIS_DATA)),
        out_specs=(param_spec_tree, opt_spec_tree, dict(sync_specs), P()),
        check_vma=False)
    # Donation decision proven above (schedule/read-after-donate): sync
    # state is donated only when every entry is a bucket residual or the
    # numerics scalars — both rewritten unconditionally every step.
    step_fn = jax.jit(mapped,
                      donate_argnums=(0, 1, 2) if donate_sync else (0, 1))

    init_opt_fn = jax.jit(init_opt, out_shardings=opt_sh_tree)
    return (step_fn, init_opt_fn, init_sync_state, param_sh_tree,
            opt_sh_tree, list(rs_buckets), ir)
