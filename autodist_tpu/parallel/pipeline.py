"""Pipeline parallelism: microbatched ring schedules over the ``pipe`` axis.

Absent from the reference (SURVEY §2.8: pipeline parallelism NO); new
first-class scope for the TPU build.

Design (the SPMD "pipelining on a mesh" formulation, cf. the scaling-book
collective-matmul recipe rather than torch-style per-rank stage processes):

* Stage parameters are *stacked*: every stage-local parameter carries a
  leading ``[num_stages]`` axis, sharded over ``pipe`` — so the strategy
  layer sees ordinary variables whose PartitionSpec leads with ``pipe``.
* The whole pipeline runs inside ``shard_map`` manual over ``pipe``: one
  ``lax.scan`` over the schedule's ticks; each tick every device applies
  its current stage to its current activation, then the activations rotate
  one hop along the ring via ``ppermute`` (nearest neighbor on ICI).
  Stage 0 injects fresh microbatches; the last stage banks results.
* Backward is ``jax.grad`` through the scan — XLA reverses the ppermute
  ring automatically.

Schedules (both fall out of ONE tick formula, see ``_chunk_at``):

* **GPipe** (``num_virtual_stages=1``): M microbatches through S stages in
  ``M + S - 1`` ticks → bubble fraction ``(S-1)/(M+S-1)``.  The default
  ``num_microbatches ≈ 4·S`` keeps that under ~20%.
* **Interleaved / circular** (``num_virtual_stages=V``, the Megatron-LM
  interleaved schedule, arxiv 2104.04473): each device holds V *chunks* of
  ``depth/(S·V)`` layers; global stage ``v·S + d`` lives on device ``d``.
  Activations circulate the ring V times; ticks = ``M·V + S - 1`` of
  ``1/V``-size stage work each → bubble ``(S-1)/(M·V + S-1)``, a V× cut
  for the same microbatch count.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from autodist_tpu.const import MESH_AXIS_PIPE


def interleaved_stage_order(num_stages: int, num_virtual_stages: int
                            ) -> Tuple[int, ...]:
    """Device-major permutation of pipeline-order stage indices.

    For V>1 ``pipeline_apply`` expects the stage axis laid out device-major
    — entry ``d·V + v`` is global stage ``v·S + d`` — so the compiler's
    contiguous sharding of the leading axis over ``pipe`` puts each device's
    V chunks on it with NO per-step resharding.  Apply this permutation to a
    pipeline-ordered stage list before ``stack_stage_params``."""
    s, v = num_stages, num_virtual_stages
    return tuple(vv * s + d for d in range(s) for vv in range(v))


def schedule_ticks(num_stages: int, num_microbatches: int,
                   num_virtual_stages: int = 1) -> int:
    """Total ring ticks the schedule takes.

    The last microbatch (index M-1) is injected at tick
    ``((M-1)//S)·S·V + (M-1)%S`` (device 0 accepts a fresh microbatch only
    when an empty ring slot arrives) and exits ``S·V`` ticks later."""
    s, m, v = num_stages, num_microbatches, num_virtual_stages
    return ((m - 1) // s) * s * v + ((m - 1) % s) + s * v


def bubble_fraction(num_stages: int, num_microbatches: int,
                    num_virtual_stages: int = 1) -> float:
    """Idle fraction of the schedule: 1 − ideal_ticks / actual_ticks, where
    ideal = M·V ticks of chunk-sized work."""
    t = schedule_ticks(num_stages, num_microbatches, num_virtual_stages)
    return 1.0 - (num_microbatches * num_virtual_stages) / t


def default_num_microbatches(num_stages: int, batch: int) -> int:
    """Largest feasible microbatch count ≤ 4·S — the GPipe bubble at 4·S is
    (S-1)/(5S-1) < 20% (vs ~50% at the pipe-filling minimum M=S)."""
    m = min(4 * num_stages, batch)
    while batch % m:
        m -= 1
    return m


def pipeline_apply(stage_fn: Callable, stage_params: Any, x: jax.Array,
                   mesh: Mesh, *, num_microbatches: Optional[int] = None,
                   num_virtual_stages: int = 1, remat: bool = False,
                   axis_name: str = MESH_AXIS_PIPE) -> jax.Array:
    """Apply a pipeline of stacked stages to a batch.

    Args:
      stage_fn: ``(params_one_stage, x_microbatch) -> y_microbatch`` with
        ``y`` shaped like ``x`` (inter-stage activations must be homogeneous
        — true of transformer stacks).  Must be a *stable* callable: the
        compiled schedule is cached keyed on its identity, so passing a
        fresh closure/partial per call recompiles (and grows the cache)
        every time.
      stage_params: pytree whose leaves lead with a ``[S·V]`` stage axis —
        pipeline order for V=1; **device-major** for V>1 (entry ``d·V + v``
        = global stage ``v·S + d``; see :func:`interleaved_stage_order`), so
        contiguous ``pipe`` sharding of the axis lands each device's chunks
        on it without any per-step resharding.
      x: global batch ``[B, ...]``; must divide into ``num_microbatches``.
      num_microbatches: defaults to the largest feasible count ≤ ``4·S``.
      num_virtual_stages: chunks per device (interleaved schedule); the
        stage axis must equal ``S · num_virtual_stages``.
      remat: rematerialize each stage application in the backward pass.
        Differentiating the tick-scan stashes every tick's stage-internal
        activations for the whole schedule — the GPipe memory profile; with
        ``remat`` only the tick BOUNDARY activations are stashed and stage
        internals recompute during backward, trading ~1 extra forward of
        FLOPs for an O(depth/S) cut in stashed bytes per device (the
        scan-boundary memory shape 1F1B targets, achieved here within
        whole-program autodiff instead of a hand-scheduled backward).

    Returns ``[B, ...]`` after all stages.
    """
    s = mesh.shape.get(axis_name, 1)
    v = num_virtual_stages
    if s <= 1:
        # No pipe axis: sequential scan over the stage dimension.  With
        # S=1 the device-major layout coincides with pipeline order, so no
        # reordering is needed.
        fn = jax.checkpoint(stage_fn) if remat else stage_fn

        def body(h, p):
            return fn(p, h), None
        out, _ = lax.scan(body, x, stage_params)
        return out

    b = x.shape[0]
    m = num_microbatches or default_num_microbatches(s, b)
    if b % m:
        raise ValueError(f"batch {b} not divisible into {m} microbatches")
    for leaf in jax.tree_util.tree_leaves(stage_params):
        if leaf.shape[0] != s * v:
            raise ValueError(
                f"stage_params leading dim {leaf.shape[0]} != pipe axis "
                f"size {s} x {v} virtual stages")

    # Device-major [S·V] → [S, V]: row d = device d's V chunks.  A plain
    # reshape, and contiguous 'pipe' sharding of the stored axis is exactly
    # the sharding of dim 0 here — no data movement.
    chunk_params = jax.tree_util.tree_map(
        lambda p: p.reshape((s, v) + p.shape[1:]), stage_params)
    return _jitted_pipeline(stage_fn, mesh, m, v, remat,
                            axis_name)(chunk_params, x)


@functools.lru_cache(maxsize=None)
def _jitted_pipeline(stage_fn: Callable, mesh: Mesh, num_microbatches: int,
                     num_virtual: int, remat: bool,
                     axis_name: str) -> Callable:
    # Cache note: keyed on stage_fn identity — callers must pass a stable
    # callable (the bundled models create stage_fn once per ModelSpec).
    if remat:
        stage_fn = jax.checkpoint(stage_fn)
    local = functools.partial(_pipeline_local, stage_fn, axis_name=axis_name,
                              num_microbatches=num_microbatches,
                              num_virtual=num_virtual)
    # Partial-manual: only the pipe axis is manualized; data/model sharding
    # of the batch and stage params stays with GSPMD.  jit (inlined when the
    # caller already traces) because eager shard_map with partial axis_names
    # trips JAX's internal unmatch path — same workaround as
    # ops/flash_attention.make_flash_attention.
    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis_name), P()), out_specs=P(),
        axis_names={axis_name}, check_vma=False,
    ))


def _pipeline_local(stage_fn: Callable, chunk_params: Any, x: jax.Array, *,
                    axis_name: str, num_microbatches: int,
                    num_virtual: int) -> jax.Array:
    """Per-device schedule loop (inside shard_map over ``axis_name``).

    One tick formula covers GPipe and interleaved: the activation at device
    ``d`` on tick ``t`` is on chunk ``v(d,t) = ((t-d) mod S·V) // S``.
    Device 0 injects a fresh microbatch whenever the arriving ring slot is
    empty (``v=0``); the last device banks whenever it finishes ``v=V-1``.
    """
    s = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    m = num_microbatches
    nv = num_virtual
    period = s * nv
    # chunk_params local shape [1, V, ...]: squeeze the device dim.
    params = jax.tree_util.tree_map(lambda p: jnp.squeeze(p, 0), chunk_params)

    mb = x.reshape((m, x.shape[0] // m) + x.shape[1:])  # [M, mb, ...]
    zero = jnp.zeros_like(mb[0])
    # Rotate forward: stage i sends to stage i+1 (ring; the wraparound
    # advances the activation to the device's next chunk).
    perm = [(i, (i + 1) % s) for i in range(s)]

    def tick(carry, t):
        acc, a_in = carry
        v = jnp.mod(t - idx, period) // s           # this device's chunk now
        # Device 0 injects microbatch j when an empty slot arrives (v == 0).
        j = (t // period) * s + jnp.mod(t, period)
        inject = jnp.logical_and(idx == 0, jnp.mod(t, period) < s)
        feed = lax.dynamic_index_in_dim(mb, jnp.clip(j, 0, m - 1), 0,
                                        keepdims=False)
        a = jnp.where(inject, feed, a_in)
        p_v = jax.tree_util.tree_map(
            lambda p: lax.dynamic_index_in_dim(p, v, 0, keepdims=False),
            params)
        y = stage_fn(p_v, a)
        # Last device banks microbatch je once its final chunk completes
        # (injection tick te = t - (S·V - 1); je < m guards schedule padding
        # when M is not a multiple of S).
        te = t - (period - 1)
        je = (te // period) * s + jnp.mod(te, period)
        bank = jnp.logical_and(idx == s - 1, v == nv - 1)
        bank = jnp.logical_and(bank, jnp.logical_and(te >= 0, je < m))
        slot = jnp.clip(je, 0, m - 1)
        cur = lax.dynamic_index_in_dim(acc, slot, 0, keepdims=False)
        acc = lax.dynamic_update_index_in_dim(
            acc, jnp.where(bank, y, cur), slot, 0)
        a_next = lax.ppermute(y, axis_name, perm)
        return (acc, a_next), None

    vary = lambda v_: lax.pcast(v_, axis_name, to="varying")  # noqa: E731
    acc0 = vary(jnp.zeros_like(mb))
    ticks = schedule_ticks(int(s), m, nv)
    (acc, _), _ = lax.scan(tick, (acc0, vary(zero)), jnp.arange(ticks))
    # Only the last stage holds real outputs; zero elsewhere — a psum
    # replicates them across pipe (out_specs=P()).
    acc = lax.psum(jnp.where(idx == s - 1, acc, jnp.zeros_like(acc)),
                   axis_name)
    return acc.reshape(x.shape)


def stack_stage_params(per_stage_params) -> Any:
    """Stack a list of per-stage pytrees into one pytree with a leading
    ``[S]`` (or ``[S·V]``) axis in pipeline order (helper for hand-built
    pipelines)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                  *per_stage_params)
