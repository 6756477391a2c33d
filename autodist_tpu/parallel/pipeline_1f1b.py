"""One-forward-one-backward (1F1B) pipeline schedule with a hand-built
backward pass.

``pipeline.py`` differentiates the GPipe tick-scan with whole-program
autodiff: correct, but every microbatch's boundary activations stay
stashed until the scan's backward runs — O(M) live activations (remat
trims the per-tick internals, not the count).  The 1F1B schedule
(PipeDream-flush / Megatron-LM) interleaves each microbatch's backward
as soon as its forward clears the last stage, so a device holds at most
``2·(S−1)`` in-flight boundary activations — O(S), independent of M.

Schedule algebra (unit fwd+bwd per tick), including the **interleaved /
circular** variant (``num_virtual_stages=V``): device ``d`` holds the V
chunks at global stages ``v·S + d`` (the device-major layout shared with
``pipeline.py``), activations circulate the forward ring V times and
cotangents circulate the reverse ring V times:

* microbatch ``j`` is injected at device 0 at tick
  ``tj = (j//S)·S·V + j%S`` (S injections per ``S·V``-tick period — the
  circular-GPipe injection cadence, which keeps every device's forward
  slot dense);
* its forward runs global stage ``g = v·S + d`` at tick ``tj + g``;
* the last global stage (device S−1, chunk V−1) computes the
  per-microbatch loss AND its cotangent at the same tick its forward
  completes (``tj + SV − 1``);
* its backward runs global stage ``g`` at tick ``tj + 2(SV−1) − g`` —
  cotangents hop ``d → d−1`` on the reverse ring (the ``g ≡ 0 (mod S)``
  wraparound hop 0 → S−1 is exactly the ring's wraparound);
* every tick a device does (at most) one chunk-forward AND one
  chunk-backward: the eponymous 1F1B steady state.  Total ticks
  ``(M−1)//S·SV + (M−1)%S + 2(SV−1) + 1`` (= ``M + 2(S−1)`` at V=1).

Bubble accounting (``bubble_fraction_1f1b``): warmup+drain idle is
``SV + S − 2`` ticks of 1/V-size chunk work — in stage-work units
``S + (S−2)/V``, vs ``2(S−1)`` for V=1, so interleaving cuts the 1F1B
bubble toward its ``S``-stage-unit floor (S=4: 6 → 5 → 4.5 stage units
at V=1→2→4).  The Megatron-interleaved ``(S−1)/V`` bubble is NOT
reachable in this SPMD formulation: it needs per-device-divergent
forward/backward slots, but ``ppermute`` is a uniform collective — every
device must run the same tick body, so the floor is the ``2(SV−1)``-hop
ring latency of the last microbatch.  What interleaving buys here is the
warmup/drain HALF-idle ticks shrinking by V in work units, plus the same
O(S·V) (M-independent) activation stash.

Each device keeps a circular buffer of its saved chunk INPUTS (capacity
``2·S·V``, static; the maximum forward→backward span is ``2(SV−1)``
ticks); backward recomputes the chunk forward under ``jax.vjp`` from the
saved input — the recompute-based 1F1B every large-scale implementation
uses.

The public entry returns ``(mean_loss, d_stage_params, d_x)`` directly —
a manual value-and-grad over the pipeline — and is verified bit-close
against autodiff through ``pipeline_apply`` in ``tests/test_pipeline_1f1b.py``.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from autodist_tpu.const import MESH_AXIS_DATA, MESH_AXIS_PIPE
# The tick/bubble algebra is pure and shared with the mesh-free side
# (schedule IR pricing, the --simulate sweep, the MPMD StageRunner), so
# it lives jax-free in schedule_ir; re-exported here for compatibility.
from autodist_tpu.kernel.synchronization.schedule_ir import (  # noqa: F401
    bubble_fraction_1f1b,
    schedule_ticks_1f1b,
)


def one_f_one_b(stage_fn: Callable, loss_fn: Callable, stage_params: Any,
                x: jax.Array, targets: Any, mesh: Mesh, *,
                num_microbatches: int, loss_params: Any = None,
                num_virtual_stages: int = 1,
                axis_name: str = MESH_AXIS_PIPE):
    """Pipelined value-and-grad under the 1F1B schedule.

    Args:
      stage_fn: ``(params_one_stage, x_mb) -> y_mb``, activation-shape
        homogeneous across stages (the ``pipeline_apply`` contract).
      loss_fn: ``(y_mb, target_mb) -> scalar`` per-microbatch loss — or,
        with ``loss_params``, ``(loss_params, y_mb, target_mb) -> scalar``
        (the head/norm/logits that live AFTER the pipeline; their
        gradients accumulate on the last stage).  The total loss is the
        MEAN over microbatches.
      stage_params: pytree with a leading ``[S·V]`` stage axis — pipeline
        order for V=1, **device-major** for V>1 (entry ``d·V + v`` =
        global stage ``v·S + d``, the ``pipeline_apply`` /
        :func:`~autodist_tpu.parallel.pipeline.interleaved_stage_order`
        contract), sharded over ``axis_name``.
      x: global batch ``[B, ...]``; ``B % num_microbatches == 0``.  When
        the mesh carries a ``data`` axis the batch is data-sharded and
        the schedule composes with data parallelism: each shard runs its
        own 1F1B over its rows (``num_microbatches`` applies PER SHARD)
        and gradients/loss pmean over ``data``.
      targets: pytree of arrays with leading dim ``B`` (what ``loss_fn``
        consumes per microbatch).
      loss_params: optional pytree consumed by ``loss_fn``; replicated.
      num_virtual_stages: chunks per device (interleaved schedule — the
        module docstring's circular 1F1B); the stage axis must equal
        ``S · num_virtual_stages``.

    Returns ``(loss, d_stage_params, d_x)`` — or, with ``loss_params``,
    ``(loss, d_stage_params, d_loss_params, d_x)`` — gradients for the
    stacked stage params (same ``[S·V]``-leading layout), the loss-side
    params, and the batch input (so upstream layers, e.g. embeddings,
    keep training).
    """
    s = mesh.shape.get(axis_name, 1)
    v = num_virtual_stages
    m = num_microbatches
    b = x.shape[0]
    if v < 1:
        raise ValueError(f"num_virtual_stages must be >= 1, got {v}")
    if b % m:
        raise ValueError(f"batch {b} not divisible into {m} microbatches")
    for leaf in jax.tree_util.tree_leaves(targets):
        if leaf.shape[0] != b:
            raise ValueError(
                f"targets leading dim {leaf.shape[0]} != batch {b}")
    if m < s:
        raise ValueError(f"1F1B needs num_microbatches ({m}) >= stages ({s})")
    if s > 1:
        for leaf in jax.tree_util.tree_leaves(stage_params):
            if leaf.shape[0] != s * v:
                raise ValueError(
                    f"stage_params leading dim {leaf.shape[0]} != pipe axis "
                    f"{s} x {v} virtual stages")

    if s <= 1:
        # No pipe axis: plain scan + autodiff (nothing to schedule).
        def whole(sp, lp, x):
            def body(h, p):
                return stage_fn(p, h), None
            out, _ = lax.scan(body, x, sp)
            fn = loss_fn if loss_params is None \
                else functools.partial(loss_fn, lp)
            return jnp.mean(_loss_over_microbatches(fn, out, targets, m))
        loss, (dsp, dlp, dx) = jax.value_and_grad(whole, argnums=(0, 1, 2))(
            stage_params, loss_params, x)
        if loss_params is None:
            return loss, dsp, dx
        return loss, dsp, dlp, dx

    dp_axis = MESH_AXIS_DATA if (axis_name != MESH_AXIS_DATA and
                                 mesh.shape.get(MESH_AXIS_DATA, 1) > 1) \
        else None
    if dp_axis is not None:
        dsize = mesh.shape[MESH_AXIS_DATA]
        if b % (dsize * m):
            raise ValueError(
                f"batch {b} not divisible into {dsize} data shards x {m} "
                "microbatches")
    lp = {} if loss_params is None else loss_params
    # Device-major [S·V] → [S, V]: row d = device d's V chunks (a plain
    # reshape; contiguous 'pipe' sharding of the stored axis IS the
    # sharding of dim 0 here — no data movement).
    chunked = jax.tree_util.tree_map(
        lambda p: p.reshape((s, v) + p.shape[1:]), stage_params)
    out = _jitted_1f1b(stage_fn, loss_fn, mesh, m, v,
                       loss_params is not None, dp_axis, axis_name)(
        chunked, lp, x, targets)
    loss, dsp, dlp, dx = out
    # [S, V, ...] gradients back to the caller's [S·V, ...] layout.
    dsp = jax.tree_util.tree_map(
        lambda g, p: g.reshape(p.shape), dsp, stage_params)
    if loss_params is None:
        return loss, dsp, dx
    return loss, dsp, dlp, dx


def _loss_over_microbatches(loss_fn, out, targets, m):
    mb = out.reshape((m, out.shape[0] // m) + out.shape[1:])
    tb = jax.tree_util.tree_map(
        lambda t: t.reshape((m, t.shape[0] // m) + t.shape[1:]), targets)
    return jax.vmap(loss_fn)(mb, tb)


@functools.lru_cache(maxsize=None)
def _jitted_1f1b(stage_fn: Callable, loss_fn: Callable, mesh: Mesh,
                 num_microbatches: int, num_virtual: int,
                 has_loss_params: bool,
                 dp_axis, axis_name: str) -> Callable:
    # Cache keyed on (stage_fn, loss_fn) identity — pass stable callables
    # (same contract as pipeline._jitted_pipeline).  Partial-manual over
    # {pipe, data}: the batch additionally splits over ``dp_axis`` (each
    # data shard runs its own 1F1B over its rows; grads pmean over data),
    # while model/seq axes stay with GSPMD inside stage_fn.
    local = functools.partial(_local_1f1b, stage_fn, loss_fn,
                              axis_name=axis_name, m=num_microbatches,
                              nv=num_virtual,
                              has_lp=has_loss_params, dp_axis=dp_axis)
    bspec = P(dp_axis) if dp_axis else P()
    manual = {axis_name} | ({dp_axis} if dp_axis else set())
    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis_name), P(), bspec, bspec),
        out_specs=(P(), P(axis_name), P(), bspec),
        axis_names=manual, check_vma=False,
    ))


def _local_1f1b(stage_fn: Callable, loss_fn: Callable, chunk_params: Any,
                loss_params: Any, x: jax.Array, targets: Any, *,
                axis_name: str, m: int, nv: int, has_lp: bool, dp_axis=None):
    """Per-device 1F1B loop (inside full-manual shard_map): ``x`` and
    ``targets`` arrive as this data shard's rows (replicated over the
    pipe axis); the schedule runs over the LOCAL rows, and gradients /
    loss pmean over ``dp_axis`` at the end.

    Schedule index algebra (module docstring): microbatch ``j`` is
    injected at ``tj = (j//S)·SV + j%S``; its forward at global stage
    ``g = v·S + d`` runs at tick ``tj + g`` and its backward at tick
    ``tj + 2(SV−1) − g``.  Inverting for (tick, device) gives exactly one
    forward chunk ``vf`` and one backward chunk ``vb`` per device per
    tick — both streams ride one uniform ppermute pair."""
    s = lax.axis_size(axis_name)
    d = lax.axis_index(axis_name)
    period = s * nv
    # chunk_params local shape [1, V, ...]: squeeze the device dim.
    params = jax.tree_util.tree_map(lambda p: jnp.squeeze(p, 0), chunk_params)

    mb = x.reshape((m, x.shape[0] // m) + x.shape[1:])       # [M, mb, ...]
    tgt = jax.tree_util.tree_map(
        lambda t: t.reshape((m, t.shape[0] // m) + t.shape[1:]), targets)
    zero_a = jnp.zeros_like(mb[0])
    k = 2 * s * nv                                            # stash slots
    stash0 = jnp.zeros((k,) + mb[0].shape, mb.dtype)
    dparams0 = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)    # [V, ...]
    dx0 = jnp.zeros_like(mb, jnp.float32)                     # [M, mb, ...]
    dlp0 = jax.tree_util.tree_map(
        lambda p: jnp.zeros(jnp.shape(p), jnp.float32), loss_params)

    fwd_perm = [(i, (i + 1) % s) for i in range(s)]
    bwd_perm = [(i, (i - 1) % s) for i in range(s)]
    vary = lambda v: lax.pcast(v, axis_name, to="varying")  # noqa: E731
    ticks = schedule_ticks_1f1b(int(s), m, nv)

    def chunk_at(v):
        return jax.tree_util.tree_map(
            lambda p: lax.dynamic_index_in_dim(p, v, 0, keepdims=False),
            params)

    def stage_vjp(p, xin, ct):
        y, pullback = jax.vjp(lambda pp, xx: stage_fn(pp, xx), p, xin)
        dp, dxin = pullback(ct.astype(y.dtype))
        return dp, dxin

    def tick(carry, t):
        a_in, g_in, stash, dparams, dlp, dx_bank, loss_acc = carry

        # ---- forward phase ------------------------------------------------
        # Chunk this device forwards now, the mb it belongs to, and its
        # injection tick (mod-arithmetic inversion; garbage when inactive).
        vf = jnp.mod(t - d, period) // s
        gf = vf * s + d                              # global stage
        tjf = t - gf                                 # injection tick
        jf = (tjf // period) * s + jnp.mod(tjf, s)   # mb this device fwd's
        active_f = jnp.logical_and(tjf >= 0, jf < m)
        feed = lax.dynamic_index_in_dim(mb, jnp.clip(jf, 0, m - 1), 0,
                                        keepdims=False)
        x_in = jnp.where(jnp.logical_and(d == 0, vf == 0), feed, a_in)
        y = stage_fn(chunk_at(vf), x_in)
        # save this tick's chunk INPUT for the backward recompute
        slot_f = jnp.mod(t, k)
        cur = lax.dynamic_index_in_dim(stash, slot_f, 0, keepdims=False)
        stash = lax.dynamic_update_index_in_dim(
            stash, jnp.where(active_f, x_in, cur), slot_f, 0)

        # last global stage (device S-1, chunk V-1): per-microbatch loss +
        # its cotangent, entering the backward stream THIS tick (bwd of mb
        # jf at stage SV-1 is tick tjf + 2(SV-1) - (SV-1) = tjf + SV-1 = t).
        tgt_j = jax.tree_util.tree_map(
            lambda tt: lax.dynamic_index_in_dim(
                tt, jnp.clip(jf, 0, m - 1), 0, keepdims=False), tgt)
        is_last = jnp.logical_and(d == s - 1, vf == nv - 1)
        if has_lp:
            loss_j, loss_pull = jax.vjp(
                lambda lp, yy: loss_fn(lp, yy, tgt_j), loss_params, y)
            dlp_j, dy_loss = loss_pull(jnp.float32(1.0 / m))
            # loss-side param grads accumulate on the LAST stage only, at
            # the microbatch's loss tick (where-mask: see below).
            last_active = jnp.logical_and(is_last, active_f)
            dlp = jax.tree_util.tree_map(
                lambda a, g: a + jnp.where(last_active,
                                           g.astype(jnp.float32), 0.0),
                dlp, dlp_j)
        else:
            loss_j, loss_pull = jax.vjp(lambda yy: loss_fn(yy, tgt_j), y)
            (dy_loss,) = loss_pull(jnp.float32(1.0 / m))
        loss_acc = loss_acc + jnp.where(
            jnp.logical_and(is_last, active_f), loss_j / m, 0.0)

        # ---- backward phase ----------------------------------------------
        # Invert tb = tj + 2(SV-1) - g for (t, d): vb is the unique chunk
        # with (t + d - 2(SV-1) + vb·S) an injection tick (mod period < S).
        u = t + d - 2 * (s * nv - 1)
        vb = jnp.mod(-(jnp.mod(u, period) // s), nv)
        gb = vb * s + d
        tjb = u + vb * s
        jb = (tjb // period) * s + jnp.mod(tjb, s)   # mb this device bwd's
        active_b = jnp.logical_and(tjb >= 0, jb < m)
        # cotangent: locally generated at the last global stage, ring-
        # arriving everywhere else
        fresh_ct = jnp.logical_and(d == s - 1, vb == nv - 1)
        ct = jnp.where(fresh_ct, dy_loss.astype(jnp.float32),
                       g_in.astype(jnp.float32))
        # retrieve the saved chunk input of mb jb (saved at tick tjb + gb)
        slot_b = jnp.mod(tjb + gb, k)
        x_saved = lax.dynamic_index_in_dim(stash, slot_b, 0, keepdims=False)
        dp, dxin = stage_vjp(chunk_at(vb), x_saved, ct)
        # where-mask, not multiply: inactive ticks can compute on garbage
        # (NaN-capable) values, and 0 * NaN = NaN would poison the sums.
        dparams = jax.tree_util.tree_map(
            lambda a, g: a.at[vb].add(
                jnp.where(active_b, g.astype(jnp.float32), 0.0)),
            dparams, dp)
        # device 0 chunk 0's dxin is the gradient w.r.t. the injected mb
        bank = jnp.logical_and(jnp.logical_and(d == 0, vb == 0), active_b)
        slot_x = jnp.clip(jb, 0, m - 1)
        cur_dx = lax.dynamic_index_in_dim(dx_bank, slot_x, 0, keepdims=False)
        dx_bank = lax.dynamic_update_index_in_dim(
            dx_bank, jnp.where(bank, dxin.astype(jnp.float32), cur_dx),
            slot_x, 0)

        a_next = lax.ppermute(y, axis_name, fwd_perm)
        g_next = lax.ppermute(dxin.astype(jnp.float32), axis_name, bwd_perm)
        return (a_next, g_next, stash, dparams, dlp, dx_bank, loss_acc), None

    carry0 = (vary(zero_a), vary(jnp.zeros_like(zero_a, jnp.float32)),
              vary(stash0), vary(dparams0), vary(dlp0), vary(dx0),
              vary(jnp.float32(0)))
    (a, g, stash, dparams, dlp, dx_bank, loss_acc), _ = lax.scan(
        tick, carry0, jnp.arange(ticks))

    # loss lives on the last device; dx on device 0 — replicate via psum.
    loss = lax.psum(jnp.where(d == s - 1, loss_acc, 0.0), axis_name)
    dx = lax.psum(jnp.where(d == 0, dx_bank, jnp.zeros_like(dx_bank)),
                  axis_name)
    dx = dx.reshape((dx.shape[0] * dx.shape[1],) + dx.shape[2:])
    # loss-side grads live on the last device; replicate over pipe.
    dlp = jax.tree_util.tree_map(
        lambda g: lax.psum(jnp.where(d == s - 1, g, jnp.zeros_like(g)),
                           axis_name), dlp)
    if dp_axis is not None:
        # Each data shard computed d(mean over ITS rows); the global loss
        # is the mean over shards, so everything averages over data —
        # except dx, whose rows are shard-local: scale by 1/D.
        dsize = lax.axis_size(dp_axis)
        loss = lax.pmean(loss, dp_axis)
        dparams = jax.tree_util.tree_map(
            lambda g: lax.pmean(g, dp_axis), dparams)
        dlp = jax.tree_util.tree_map(lambda g: lax.pmean(g, dp_axis), dlp)
        dx = dx / dsize
    # Accumulation ran in f32; return grads in the primal dtypes (what
    # autodiff — and the s==1 fallback — would produce).
    dx = dx.astype(x.dtype)
    # dparams stays device-local: out_specs P(axis_name) restacks the [S]
    # axis exactly like the incoming stage_params layout.
    dparams = jax.tree_util.tree_map(
        lambda g, p: g[None].astype(p.dtype), dparams, params)
    dlp = jax.tree_util.tree_map(
        lambda g, p: g.astype(jnp.result_type(p)), dlp, loss_params)
    return loss, dparams, dlp, dx
