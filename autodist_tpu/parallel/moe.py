"""Expert parallelism: mixture-of-experts FFN over the ``expert`` mesh axis.

Absent from the reference (SURVEY §2.8: EP/MoE NO); new first-class scope.

Formulation: GShard/Switch-style capacity-based routing (Lepikhin et al.
2020, arxiv 2006.16668) expressed as dense einsums over one-hot dispatch/
combine tensors — the TPU-idiomatic MoE: static shapes (capacity bounds the
per-expert token count), MXU-friendly batched expert matmuls, and GSPMD
inserts the expert all-to-alls from the sharding constraints alone
(expert-major tensors lead with the ``expert`` axis; no hand-written
``lax.all_to_all`` needed, though the layout is exactly the all-to-all
dispatch of DeepSpeed-MoE/Tutel-style implementations).

Router runs in fp32 (bf16 softmax over experts is noisy enough to flip
top-k decisions).  The auxiliary load-balancing loss is returned to the
caller — models fold it into the training loss.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from autodist_tpu.const import MESH_AXIS_DATA, MESH_AXIS_EXPERT
from autodist_tpu.ops import grouped_matmul
from autodist_tpu.ops.rows_to_tokens import rows_to_tokens
from autodist_tpu.utils import logging

#: capacity configs already warned about (one line per distinct config,
#: not one per trace).
_warned_capacity: set = set()


def moe_wire_format(wire: Optional[str] = None):
    """Resolve the expert-a2a wire format: the explicit ``wire`` arg
    ("int8" / a compressor name) wins, else the shared
    ``AUTODIST_MOE_WIRE`` knob — the SAME default the schedule IR's
    :func:`~autodist_tpu.kernel.synchronization.schedule_ir.
    moe_wire_compressor_default` reads, so the legs' priced wire bytes
    and the runtime payload cannot disagree.  Returns a
    ``quant_ring.WireFormat`` or None (full-precision wire)."""
    from autodist_tpu.kernel.synchronization import quant_ring, schedule_ir

    name = wire if wire is not None \
        else schedule_ir.moe_wire_compressor_default()
    if not name or name == "NoneCompressor":
        return None
    if name == "int8":
        name = "Int8Compressor"
    fmt = quant_ring.wire_format_of(name)
    if fmt is None:
        raise ValueError(f"moe wire {name!r} has no quantized wire format")
    return fmt


def init_moe_params(rng, d_model: int, d_ff: int, num_experts: int,
                    dtype=jnp.float32) -> dict:
    """Router + stacked expert FFN weights (leading ``[E]`` axis — flag these
    via ``expert_vars`` so the compiler shards it over ``expert``)."""
    r_router, r_wi, r_wo = jax.random.split(rng, 3)
    scale_in = 1.0 / (d_model ** 0.5)
    scale_out = 1.0 / (d_ff ** 0.5)
    return {
        "router": (jax.random.normal(r_router, (d_model, num_experts),
                                     jnp.float32) * scale_in),
        "wi": (jax.random.normal(r_wi, (num_experts, d_model, d_ff),
                                 dtype) * scale_in),
        "wo": (jax.random.normal(r_wo, (num_experts, d_ff, d_model),
                                 dtype) * scale_out),
    }


def _top2_dispatch(probs: jax.Array, capacity: int
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """probs [G, S, E] → (dispatch [G,S,E,C] bool, combine [G,S,E,C], aux).

    G = groups (batch), S = tokens per group, E = experts, C = capacity.
    Tokens overflowing an expert's capacity within their group are dropped
    (their combine weight is zero — the residual connection carries them).
    """
    g, s, e = probs.shape

    idx1 = jnp.argmax(probs, axis=-1)                       # [G,S]
    mask1 = jax.nn.one_hot(idx1, e, dtype=probs.dtype)      # [G,S,E]
    probs_wo1 = probs * (1.0 - mask1)
    idx2 = jnp.argmax(probs_wo1, axis=-1)
    mask2 = jax.nn.one_hot(idx2, e, dtype=probs.dtype)
    if e == 1:
        # Single expert: argmax over all-zero probs_wo1 re-selects expert 0,
        # which would double-book two capacity slots per token.
        mask2 = jnp.zeros_like(mask2)

    # Positions within each expert's buffer, first-come-first-served along
    # the token axis; second choices queue after all first choices.
    pos1 = jnp.cumsum(mask1, axis=1) - mask1                # [G,S,E]
    pos2 = jnp.cumsum(mask2, axis=1) - mask2 \
        + jnp.sum(mask1, axis=1, keepdims=True)
    keep1 = mask1 * (pos1 < capacity)
    keep2 = mask2 * (pos2 < capacity)

    w1 = jnp.sum(probs * keep1, axis=-1)                    # [G,S]
    w2 = jnp.sum(probs * keep2, axis=-1)
    denom = jnp.maximum(w1 + w2, 1e-9)
    w1, w2 = w1 / denom, w2 / denom

    oh1 = jax.nn.one_hot(jnp.sum(pos1 * keep1, axis=-1).astype(jnp.int32),
                         capacity, dtype=probs.dtype)       # [G,S,C]
    oh2 = jax.nn.one_hot(jnp.sum(pos2 * keep2, axis=-1).astype(jnp.int32),
                         capacity, dtype=probs.dtype)
    combine = (w1[..., None, None] * keep1[..., None] * oh1[:, :, None]
               + w2[..., None, None] * keep2[..., None] * oh2[:, :, None])
    dispatch = combine > 0.0                                # [G,S,E,C]

    # Load-balancing aux loss (GShard eq. 4): fraction of tokens routed to
    # each expert × mean router probability, summed over experts, scaled E.
    frac = jnp.mean(mask1, axis=1)                          # [G,E]
    prob_mean = jnp.mean(probs, axis=1)                     # [G,E]
    aux = jnp.mean(jnp.sum(frac * prob_mean, axis=-1)) * e
    return dispatch, combine, aux


@functools.partial(jax.jit, static_argnames=("fmt", "sharding"))
def _quantized_a2a(t: jax.Array, *, fmt, sharding) -> jax.Array:
    """Quantize, cross the expert a2a boundary as wire payload, and
    dequantize.  One jitted program, so the flat reshapes of the
    expert-sharded ``t`` stay inside GSPMD: run eagerly under
    ``jax.set_mesh``, each op must name its result's sharding on the
    mesh, and the flattened ``[E, G, ...]`` layout has none (jax 0.9.0
    raises from ``_gspmd_to_named_sharding_via_mesh``)."""
    from autodist_tpu.kernel.synchronization import quant_ring

    q, scales, _ = quant_ring.quantize_blocks(
        t.astype(jnp.float32).reshape(-1), fmt)
    q = jax.lax.with_sharding_constraint(q.reshape(t.shape), sharding)
    deq = quant_ring.dequantize_blocks(q.reshape(-1), scales)
    return deq.reshape(t.shape).astype(t.dtype)


def moe_ffn(params: dict, x: jax.Array, *,
            capacity_factor: float = 2.0,
            mesh: Optional[Mesh] = None,
            activation=jax.nn.gelu,
            wire: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """Top-2 routed expert FFN.

    Args:
      params: dict from :func:`init_moe_params`.
      x: ``[batch, seq, d_model]``.
      capacity_factor: expert buffer size = ``cf · S / E`` per group.
      mesh: optional — adds sharding constraints so expert-major
        intermediates shard over ``expert`` (and groups over ``data``),
        making GSPMD lower the dispatch/combine einsums to all-to-alls.
      wire: expert-a2a wire format ("int8"); None reads the shared
        ``AUTODIST_MOE_WIRE`` knob.  A quantized wire crosses the a2a
        boundary as int8 payload + per-block f32 scales on the
        ``quant_ring`` scale grid and dequantizes on arrival — grid-
        exact inputs round-trip bit-exactly.

    Returns ``(y [batch, seq, d_model], aux_loss scalar)``.
    """
    g, s, m = x.shape
    e = params["router"].shape[-1]
    capacity = max(1, int(capacity_factor * s / e))

    # The runtime half of the moe/capacity-overflow lint: the SAME pure
    # rule the schedule verifier applies to the IR's MoE facts.
    from autodist_tpu.kernel.synchronization.schedule_ir import (
        RULE_CAPACITY_OVERFLOW,
        moe_capacity_drop_fraction,
    )
    drop = moe_capacity_drop_fraction(capacity_factor, s, e)
    cfg = (float(capacity_factor), int(s), int(e))
    if drop > 0 and cfg not in _warned_capacity:
        _warned_capacity.add(cfg)
        logging.warning(
            "%s: capacity_factor=%g keeps %d slots/expert for balanced "
            "top-2 demand of %.0f over %d experts — ~%.0f%% of routed "
            "tokens will be dropped to the residual path",
            RULE_CAPACITY_OVERFLOW, capacity_factor, capacity,
            2.0 * s / e, e, drop * 100.0)
    fmt = moe_wire_format(wire)

    logits = jnp.einsum("gsm,me->gse", x.astype(jnp.float32),
                        params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    dispatch, combine, aux = _top2_dispatch(probs, capacity)
    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(x.dtype)

    ep_sharding = None
    if mesh is not None and mesh.shape.get(MESH_AXIS_EXPERT, 1) > 1:
        # Inside a partial-manual shard_map (e.g. the 1F1B schedule,
        # manual over pipe/data) a constraint may only name AUTO axes —
        # drop any axis the current trace has manualized (it is already
        # device-local there).
        manual = set(jax.sharding.get_abstract_mesh().manual_axes)
        if MESH_AXIS_EXPERT in manual:
            ep_sharding = None
        else:
            data_ok = (mesh.shape.get(MESH_AXIS_DATA, 1) > 1
                       and MESH_AXIS_DATA not in manual
                       and g % mesh.shape[MESH_AXIS_DATA] == 0)
            ep_sharding = NamedSharding(mesh, P(
                MESH_AXIS_EXPERT, MESH_AXIS_DATA if data_ok else None))

    def a2a(t: jax.Array) -> jax.Array:
        """Cross the expert a2a boundary: quantize-at-the-wire when a
        wire format is active (the sharding constraint lands on the
        int8 payload, so GSPMD's all-to-all ships 1/4 the bytes plus
        the per-block scale grid), plain constraint otherwise."""
        if ep_sharding is None:
            return t
        if fmt is None:
            return jax.lax.with_sharding_constraint(t, ep_sharding)
        return _quantized_a2a(t, fmt=fmt, sharding=ep_sharding)

    expert_in = a2a(jnp.einsum("gsec,gsm->egcm", dispatch, x))  # [E,G,C,M]
    h = activation(jnp.einsum("egcm,emf->egcf", expert_in, params["wi"]))
    expert_out = a2a(jnp.einsum("egcf,efm->egcm", h, params["wo"]))
    y = jnp.einsum("gsec,egcm->gsm", combine, expert_out)
    return y, aux


# ---------------------------------------------------------------------------
# k of E routing with no token dropped, for the experts HELD here
# ---------------------------------------------------------------------------
def init_routed_moe_params(rng, d_model: int, d_expert: int,
                           num_experts: int, *, experts_held: int = None,
                           d_shared: int = 0, selection_bias: bool = True,
                           shared_gate: bool = False,
                           dtype=jnp.float32) -> dict:
    """Router over all ``num_experts``, its selection bias (unless
    ``selection_bias`` is off: a softmax router has none), the SwiGLU
    weights of the ``experts_held`` experts that live here (leading axis:
    flag ``*/experts/*`` via ``expert_vars``) and, if ``d_shared``, one
    dense SwiGLU of that width (the shared experts side by side);
    ``shared_gate``: and the ``[d_model, 1]`` column whose sigmoid, one
    number a token, its output is multiplied by."""
    held = num_experts if experts_held is None else experts_held
    r = jax.random.split(rng, 8)

    def normal(key, *shape, kind=dtype):
        return jax.random.normal(key, shape, kind) * 0.02

    params = {
        "router": normal(r[0], d_model, num_experts, kind=jnp.float32),
        "router_bias": normal(r[1], num_experts, kind=jnp.float32),
        "experts": {"w_gate": normal(r[2], held, d_model, d_expert),
                    "w_up": normal(r[3], held, d_model, d_expert),
                    "w_down": normal(r[4], held, d_expert, d_model)},
    }
    if not selection_bias:
        del params["router_bias"]
    if d_shared:
        params["shared"] = {"w_gate": normal(r[5], d_model, d_shared),
                            "w_up": normal(r[6], d_model, d_shared),
                            "w_down": normal(r[7], d_shared, d_model)}
    if shared_gate:
        params["shared_gate"] = normal(jax.random.fold_in(rng, 8), d_model, 1)
    return params


def swiglu(w: dict, x: jax.Array) -> jax.Array:
    """``w_down(silu(w_gate x) * w_up x)``."""
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


#: The integers a routed layer's ``top_k`` and sorts produce (the picks
#: ``[N, k]``, the order of the ``N * k`` rows by local expert, its inverse
#: and the group sizes) carry these names: a ``jax.checkpoint`` policy that
#: keeps them (``save_only_these_names``) recomputes the layer without
#: selecting or sorting again.  They carry no gradient; the float path of
#: the router (scores, picked scores, weights) does and is not tagged.
ROUTING_RESIDUAL_NAMES = ("routed_moe/chosen", "routed_moe/order",
                          "routed_moe/inverse", "routed_moe/sizes")


def routed_rows(tokens: int, top_k: int, held: int, total: int):
    """(rows the grouped products are handed AT MOST, rows expected to be
    routed here) for one call of :func:`routed_moe_ffn` over ``tokens``
    tokens: every pick of every token can have its row, because all of a
    token's picks may lie here (every chunk of the sorted order taken);
    ``held / total`` of them do if the router spreads evenly.  What a call
    IS handed is the chunks its routing takes (:func:`budgets_taken`)."""
    return tokens * top_k, tokens * top_k * held / total


#: rows a tile of the grouped products (``ops/grouped_matmul.py``)
_GROUPED_TILE = grouped_matmul.ROW_TILE


def chunk_rows(rows: int, held: int, total: int,
               cap: Optional[int] = None) -> int:
    """``C``: the places of the sorted order one chunk of a call of
    :func:`routed_moe_ffn` over ``rows = tokens * top_k`` picks takes, from
    the shapes alone: twice the ``held / total`` of them an even router
    sends here, at most ``cap`` (the picks of one slice or sequence of the
    call: no buffer of the layer is wider) and at most ``rows``, a whole
    number of the grouped product's 512-row tiles where ``rows`` is (of 8
    rows otherwise).  All experts held, or half: ``min(cap, rows)``."""
    tile = _GROUPED_TILE if rows % _GROUPED_TILE == 0 else 8
    chunk = min(-(-2 * rows * held // total), rows if cap is None else cap)
    return min(-(-chunk // tile) * tile, rows)


def budgets_taken(tokens_per_expert: jax.Array, rows: int, total: int,
                  cap: Optional[int] = None
                  ) -> Tuple[Tuple[int, ...], jax.Array]:
    """``(rungs, calls [len(rungs)] int32)``: the rows the chunks of a call
    of :func:`routed_moe_ffn` over ``rows`` picks can cover (``C, 2 C, ..``
    up to every pick; ``cap`` as :func:`chunk_rows` takes it), and how
    many of the calls whose ``tokens_per_expert`` are stacked in ``[...,
    count]`` covered each.  The same rule the layer applies to the same
    integers: a model sums this over its calls OUTSIDE their checkpoints
    and maps."""
    chunk = chunk_rows(rows, tokens_per_expert.shape[-1], total, cap)
    rungs = tuple(range(chunk, rows + chunk, chunk))
    taken = _further_chunks(tokens_per_expert.sum(axis=-1), chunk).reshape(-1)
    return rungs, jnp.sum(taken[:, None] == jnp.arange(len(rungs)), axis=0,
                          dtype=jnp.int32)


_ROWS_HELP = ("rows the grouped expert products were handed in the last "
              "step (the chunks of the sorted order its calls took), and "
              "rows an even router would send here")


def record_row_budgets(tokens_per_expert: jax.Array, rows: int, total: int,
                       cap: Optional[int] = None) -> None:
    """For a model, once a step, from the top level of its loss function:
    ``tokens_per_expert [..., count]`` of ALL the step's calls of
    :func:`routed_moe_ffn` over ``rows`` picks each (``cap``: the picks of
    one slice or sequence of a call, as :func:`chunk_rows` takes it),
    stacked outside their checkpoints and maps.  Sets
    ``autodist_moe_rows_per_step{kind="expected"}`` now, while tracing, and
    emits the step's calls by the rows their chunks covered as a step value
    (``telemetry/step_values.py``: out with the step's metrics, no host
    callback, so the step program stays in the persistent compilation
    cache): after every step a session fetched, ``{kind="computed"}`` is
    the rows of the chunks that step's calls took and
    ``autodist_moe_row_budget_calls_total{rung=<rows>}`` has counted them;
    and ``autodist_moe_grouped_row_tiles_per_step{kind="visited"|"live"}``
    is what ``ops/grouped_matmul.py: row_tiles`` makes of the same integers.
    The loss function is to be marked ``step_values.reporting``."""
    from autodist_tpu.telemetry import registry, step_values

    rungs, calls = budgets_taken(tokens_per_expert, rows, total, cap)
    # calls x rows x held / total, and size = calls x held
    registry.gauge("autodist_moe_rows_per_step", _ROWS_HELP,
                   {"kind": "expected"}).set(
        tokens_per_expert.size * rows / total)

    def publish(calls):     # [len(rungs)], stacked over microbatches if any
        calls = calls.reshape(-1, len(rungs)).sum(axis=0).tolist()
        registry.gauge("autodist_moe_rows_per_step", _ROWS_HELP,
                       {"kind": "computed"}).set(
            sum(c * r for c, r in zip(calls, rungs)))
        for taken, rung in zip(calls, rungs):
            registry.counter(
                "autodist_moe_row_budget_calls_total",
                "calls of the routed expert layer by the rows of the "
                "sorted order their chunks covered", {"rung": str(rung)}
            ).inc(taken)

    step_values.emit("moe_row_budget_calls", calls, publish)

    # every chunk a call can take, with the cut of the group sizes the layer
    # hands its grouped products there (:func:`_sorted_rows`): nothing of a
    # chunk the call does not take
    chunk = rungs[0]
    ends = jnp.cumsum(tokens_per_expert, axis=-1)[..., None, :]
    upto = jnp.asarray(rungs)[:, None]
    within = jnp.clip(jnp.minimum(ends, upto) - jnp.maximum(
        ends - tokens_per_expert[..., None, :], upto - chunk), 0)
    tiles = jnp.stack([t.sum() for t in grouped_matmul.row_tiles(within,
                                                                 chunk)])

    def publish_tiles(tiles):           # [2], stacked over microbatches
        for kind, n in zip(("visited", "live"),
                           tiles.reshape(-1, 2).sum(axis=0).tolist()):
            registry.gauge(
                "autodist_moe_grouped_row_tiles_per_step",
                "of ONE of the forward's three grouped products, over the "
                "last step's calls and chunks: the (row tile, group) pairs "
                "its kernel visited, and the row tiles that held a routed "
                "row (visited / live is 1 plus the tiles seen twice because "
                "two groups share them; without the skipping it would be "
                "rows_per_step{computed} / 512 over live)",
                {"kind": kind}).set(n)

    step_values.emit("moe_grouped_row_tiles", tiles, publish_tiles)


def _grouped_product(rows, weights, sizes):
    """``rows [C, k] x weights [E, k, n]``, group by group: on a TPU the
    kernels of ``ops/grouped_matmul.py`` (one bfloat16 pass, float32 sums,
    their own transposes); off one XLA's ``ragged_dot`` at the ambient
    matmul precision, which the models' tests on a CPU compare at."""
    if grouped_matmul._use_interpret():
        return jax.lax.ragged_dot(rows, weights, sizes)
    return grouped_matmul.grouped_matmul(rows, weights, sizes)


def _grouped_swiglu(experts, rows, sizes, activation=jax.nn.silu):
    """:func:`swiglu` of each group of ``rows`` (``sizes`` rows each, in
    order) under its own expert's weights, the gate's ``activation`` the
    caller's.  Rows past the last group come back unwritten."""
    hidden = (activation(_grouped_product(rows, experts["w_gate"], sizes))
              * _grouped_product(rows, experts["w_up"], sizes))
    return _grouped_product(hidden, experts["w_down"], sizes)


def _further_chunks(routed, chunk: int):
    """Chunks of ``chunk`` places past the first that ``routed`` rows of
    the sorted order reach into (any shape of counts)."""
    return jnp.maximum(-(-jnp.asarray(routed) // chunk) - 1, 0)


def _sorted_rows(chunk: int, top_k: int, start, h, order, sizes):
    """Places ``[start, start + chunk)`` of the sorted order: ``(their
    tokens' rows of h [chunk, d], which of them hold a pick routed here
    [chunk, 1], their tokens [chunk], the rows of each group that lie
    among them [count], how many of them are live)``.  A place past the
    last group holds the row of some pick that is not held here: the
    grouped products leave such rows alone, so they are not cleared (a
    pass over ``[chunk, d]`` of its own)."""
    token = jax.lax.dynamic_slice_in_dim(order, start, chunk) // top_k
    ends = jnp.cumsum(sizes)
    within = jnp.clip(jnp.minimum(ends, start + chunk)
                      - jnp.maximum(ends - sizes, start), 0)
    count = within.sum()
    live = (jnp.arange(chunk) < count)[:, None]
    # every place names a token: nothing to clamp or fill
    return (h.at[token].get(mode="promise_in_bounds"), live, token, within,
            count)


def _padded(order, chunk: int):
    """``order`` with places added up to a whole number of chunks (never
    live: they name pick 0)."""
    return jnp.pad(order, (0, -order.shape[0] % chunk))


def _in_sorted_order(keys, values, chunk: int):
    """``values`` (one number a pick) moved to where ``keys`` (a
    permutation of the places) puts each, and padded to whole chunks: one
    sort of pairs.  (A gather of single numbers out of an array of a
    hundred thousand costs a TPU 25 ns each: 3 ms a call for the sort's
    0.1.)"""
    return _padded(jax.lax.sort_key_val(keys, values.reshape(-1))[1], chunk)


def _experts_on(chunk: int, top_k: int, activation, start, onto, h, experts,
                scale, order, sizes):
    """The held experts' part of the layer on places ``[start, start +
    chunk)`` of the sorted order (``scale``: the picks' weights in that
    order), back in token order and summed over the picks onto ``onto``
    (None: zeros): ``[N, d]``.  The rows that hold a pick are read once,
    each times its pick's weight, and every token is written once
    (``ops/rows_to_tokens.py``): nothing is ``N * top_k`` rows wide, a pick
    that is not held here is no row at all, and what lies past the last
    group, which a grouped product leaves UNWRITTEN, is never read.  A
    token's terms are added in the order of their experts, one after the
    other, however many chunks they lie in."""
    from autodist_tpu.telemetry import timeline

    with jax.named_scope(timeline.SCOPE_MOE_EXPERTS):
        rows, _, token, within, count = _sorted_rows(
            chunk, top_k, start, h, order, sizes)
        out = _grouped_swiglu(experts, rows, within, activation)
    with jax.named_scope(timeline.SCOPE_MOE_COMBINE):
        return rows_to_tokens(
            out, token, jax.lax.dynamic_slice_in_dim(scale, start, chunk),
            count, h.shape[0], onto=onto)


def _experts_on_transposed(chunk: int, top_k: int, activation, start, onto,
                           g, h, experts, scale, order, sizes):
    """The cotangents under :func:`_experts_on` for the cotangent ``g [N,
    d]`` of its result, on the same ``chunk`` places: ``(of h, added onto
    ``onto`` (None: zeros); of the experts; the dot of each sorted row with
    its token's g [chunk])``.  The cotangent of a sorted row is its token's
    row of ``g`` times its pick's weight, a pick's weight takes the dot of
    its sorted row with its token's ``g`` (one number a pick), and the
    cotangent of ``h`` is the sorted rows' summed by token as the
    forward's are."""
    from autodist_tpu.telemetry import timeline

    with jax.named_scope(timeline.SCOPE_MOE_EXPERTS):
        rows, live, token, within, count = _sorted_rows(
            chunk, top_k, start, h, order, sizes)
        out, transpose = jax.vjp(
            lambda experts, rows: _grouped_swiglu(experts, rows, within,
                                                  activation),
            experts, rows)
    with jax.named_scope(timeline.SCOPE_MOE_COMBINE):
        g_rows = g.at[token].get(mode="promise_in_bounds")
        d_out = jnp.where(live, g_rows * jax.lax.dynamic_slice_in_dim(
            scale, start, chunk)[:, None], 0)
        dots = jnp.sum(jnp.where(live, out, 0) * g_rows, axis=-1)
    with jax.named_scope(timeline.SCOPE_MOE_EXPERTS):
        d_experts, d_rows = transpose(d_out)
        d_h = rows_to_tokens(d_rows, token, jnp.ones_like(dots), count,
                             h.shape[0], onto=onto)
    return d_h, d_experts, dots


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _chunked_experts(top_k: int, chunk: int, activation, h, experts, weight,
                     order, inverse, sizes, here):
    """:func:`_experts_on` the first ``chunk`` places of the sorted order
    and, where the picks routed here reach past them, on each further
    ``chunk`` places in turn, by a loop whose trip count is read on the
    device (``sizes.sum()``): none in the common call.  Such a loop cannot
    be differentiated through, and a chunk's residuals would be as wide as
    the chunk: so the residuals are the INPUTS, and the backward walks the
    same chunks (the count recomputed from ``sizes``), running each one's
    forward again and its transpose."""
    from autodist_tpu.telemetry import timeline

    with jax.named_scope(timeline.SCOPE_MOE_COMBINE):
        scale = _in_sorted_order(inverse, weight, chunk)
    order = _padded(order, chunk)
    on = functools.partial(_experts_on, chunk, top_k, activation)
    return jax.lax.fori_loop(
        1, 1 + _further_chunks(sizes.sum(), chunk),
        lambda i, y: on(i * chunk, y, h, experts, scale, order, sizes),
        on(0, None, h, experts, scale, order, sizes))


def _chunked_experts_fwd(top_k, chunk, activation, *operands):
    return _chunked_experts(top_k, chunk, activation, *operands), operands


def _chunked_experts_bwd(top_k, chunk, activation, operands, g):
    from autodist_tpu.telemetry import timeline

    h, experts, weight, order, inverse, sizes, here = operands
    with jax.named_scope(timeline.SCOPE_MOE_COMBINE):
        scale = _in_sorted_order(inverse, weight, chunk)
    places = order.shape[0]
    order = _padded(order, chunk)
    on = functools.partial(_experts_on_transposed, chunk, top_k, activation)

    def further(i, carry):
        d_h, d_experts, dots = carry
        d_h, more, part = on(i * chunk, d_h, g, h, experts, scale, order,
                             sizes)
        return (d_h, jax.tree_util.tree_map(jnp.add, d_experts, more),
                jax.lax.dynamic_update_slice_in_dim(dots, part, i * chunk, 0))

    d_h, d_experts, part = on(0, None, g, h, experts, scale, order, sizes)
    d_h, d_experts, dots = jax.lax.fori_loop(
        1, 1 + _further_chunks(sizes.sum(), chunk), further,
        (d_h, d_experts, jnp.pad(part, (0, order.shape[0] - chunk))))
    with jax.named_scope(timeline.SCOPE_MOE_COMBINE):
        # one number a pick, back in the picks' order; a pick that is not
        # held here lies past the last group: whatever stands there
        d_weight = jnp.where(here, jax.lax.sort_key_val(
            order[:places], dots[:places])[1].reshape(here.shape), 0)
    # h, the experts' leaves and the weights; no cotangent for the integers
    return (d_h, d_experts, d_weight) + (None,) * 4


_chunked_experts.defvjp(_chunked_experts_fwd, _chunked_experts_bwd)


def routed_moe_ffn(params: dict, x: jax.Array, *, top_k: int,
                   experts_held: Optional[Tuple[int, int]] = None,
                   routed_scale: float = 1.0, train_router: bool = True,
                   scoring: str = "sigmoid",
                   router_input: Optional[jax.Array] = None,
                   activation=jax.nn.silu, norm_eps: float = 0.0
                   ) -> Tuple[jax.Array, jax.Array]:
    """``k`` of ``E`` routed experts with NO token dropped, for the
    experts this chip holds (DeepSeek-V3's layer, arxiv 2412.19437 §2.1.2,
    ``topk_method`` noaux_tc with one group):

        s = sigmoid(x W_r)          over all E experts, float32, highest
        S = top-k of (s + b)        b: the selection bias, no gradient
        g_e = routed_scale * s_e / (sum_{j in S} s_j + norm_eps)   e in S
        y = Shared(x) + sum_{e in S and held} g_e E_e(x)

    ``scoring="softmax"``: ``s = softmax(x W_r)`` over all ``E`` (the
    Qwen3-MoE router with ``norm_topk_prob``); where ``params`` has no
    ``router_bias`` the selection is by the scores alone, and no
    ``shared`` leaves means no shared expert; with a ``shared_gate`` leaf
    ``[d, 1]`` the shared expert's output is multiplied by ``sigmoid(x
    w_sg)``, one number a token (Qwen3-Next).  ``scoring=
    "softmax_of_picked"``: the same router computed without the softmax
    over all ``E``: the top-``k`` LOGITS are the top-``k`` of ``s`` and a
    softmax over them is ``s_e / sum_{j in S} s_j``; where the logits
    stand more than 87 apart float32 underflows ``s`` to 0 and a selection
    by ``s`` is a tie that hands every token the lowest-numbered experts.

    ``router_input`` (``x``'s leading shape, ``W_r``'s rows wide): the
    tensor the ROUTER reads where it is not the one the experts read (a
    router placed before attention reads the layer's input, its experts
    the normed stream after attention); the scores then pass their
    gradient to it and not to ``x``.  ``activation``: the experts' gate
    (``silu``: SwiGLU; ``jax.nn.relu``: ReGLU); the shared experts, where
    there are any, keep ``silu``.  ``norm_eps``: added to the sum the
    picks' scores are divided by (0: nothing is added; LFM2's public
    implementation adds 1e-6).

    ``experts_held = (first, count)``: ``params["experts"]`` leaves lead
    with ``count`` experts, which are experts ``first .. first + count``
    of the router's ``E``; the weights are normalised over all ``k`` picks
    and what the absent experts would add is left out (under expert
    parallelism it arrives by the exchange, which this function does not
    make).  None: all ``E`` are held.  ``train_router=False`` gives
    ``W_r`` no gradient (the scores still pass theirs on to ``x``): the
    selection then stays what the weights at hand make it, which is what
    a job wants whose optimizer would otherwise walk every token onto the
    same experts before any balancing could act.

    Static shapes without a capacity: the ``N * k`` (token, pick) pairs
    of ALL the call's tokens are sorted by local expert, the picks of
    absent experts last.  The first ``C`` places of that order are gathered
    and go through three grouped products (``ops/grouped_matmul.py``: on a
    TPU one kernel each, which reads the row tiles that hold a routed row
    and each expert's weights once, rounds them to bfloat16 in VMEM and
    leaves what lies past the last group alone; its transposes, the rows'
    cotangents under the weights read transposed and each group's rows
    contracted for the weights', are kernels of the same file; off a TPU
    ``jax.lax.ragged_dot``), then are added, each times its pick's weight,
    to their tokens' rows by one kernel that reads those ``C`` rows and
    writes the ``N`` tokens once (``ops/rows_to_tokens.py``; the
    backward's cotangent of ``x`` alike).  ``C`` is :func:`chunk_rows`,
    from the shapes alone:
    twice what an even router sends here, and no more than the picks of
    ``x.shape[-2]`` tokens: hand the layer ``[slices, slice, d]`` or ``[B,
    T, d]`` and no buffer of it is wider than one slice's or sequence's
    picks, whatever the routing.  Where more than ``C`` picks are routed
    here, a loop whose trip count is read ON THE DEVICE (``ceil(sizes.sum()
    / C) - 1`` further turns) takes the next ``C`` places of the order in
    turn, each with its own cut of the group sizes, and adds its part onto
    the tokens; the hand-written backward walks the same chunks.  What is
    one NUMBER a pick (the weights into sorted order, their cotangents
    back, the picked scores) moves by a sort of pairs or a comparison with
    every expert, never by a gather of single numbers.  So every
    pick of a held expert is computed: with all tokens on the held experts
    the call takes all ``N * k / C`` chunks.  Nothing but the routing
    chooses how many.  Call it ONCE for all of a step's tokens: each call
    sorts once, and each expert weight's gradient is one product a chunk.

    Returns ``(y, tokens_per_expert [count] int32)``;
    :func:`budgets_taken` of the second says which chunks the call took.
    """
    from autodist_tpu.telemetry import registry, timeline

    lead, d = x.shape[:-1], x.shape[-1]
    h = x.reshape(-1, d)
    n = h.shape[0]
    total = params["router"].shape[-1]
    experts = params["experts"]
    first, count = experts_held or (0, total)
    if experts["w_gate"].shape[0] != count or first + count > total:
        raise ValueError(
            f"experts_held={experts_held} of {total}, but the expert "
            f"leaves lead with {experts['w_gate'].shape[0]}")
    registry.gauge("autodist_moe_experts_held",
                   "experts of a routed layer computed here").set(count)
    registry.gauge("autodist_moe_experts_total",
                   "experts its router chooses among").set(total)

    router = params["router"].astype(jnp.float32)
    if not train_router:
        router = jax.lax.stop_gradient(router)
    if scoring not in ("sigmoid", "softmax", "softmax_of_picked"):
        raise ValueError(f"scoring={scoring!r}: expected 'sigmoid', "
                         f"'softmax' or 'softmax_of_picked'")
    read = h if router_input is None else router_input.reshape(n, -1)
    with jax.named_scope(timeline.SCOPE_MOE_ROUTE):
        logits = jnp.dot(read.astype(jnp.float32), router,
                         precision=jax.lax.Precision.HIGHEST)
        if scoring == "softmax_of_picked":
            scores = logits         # normalised over the picks below
        else:
            scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
                else jax.nn.softmax(logits, axis=-1)
        ranked = scores
        if "router_bias" in params:
            ranked = scores + jax.lax.stop_gradient(params["router_bias"])
        _, chosen = jax.lax.top_k(ranked, top_k)
        chosen = checkpoint_name(chosen, ROUTING_RESIDUAL_NAMES[0])
        # the picks' scores [N, k] by a comparison with every expert and a
        # sum whose terms but one are zero: exact, value and transpose; as
        # a gather of single numbers and its scatter-add a TPU takes ~10 ns
        # a pick for each
        picked = jnp.sum(jnp.where(
            chosen[..., None] == jnp.arange(total), scores[:, None], 0),
            axis=-1)
        if scoring == "softmax_of_picked":
            gates = routed_scale * jax.nn.softmax(picked, axis=-1)
        else:
            gates = routed_scale * picked
            norm = picked.sum(-1, keepdims=True)
            gates = gates / (norm + norm_eps if norm_eps else norm)
        local = chosen - first
        here = (local >= 0) & (local < count)
        group = jnp.where(here, local, count).reshape(-1)       # [N * k]
        order = jnp.argsort(group, stable=True)   # rows by local expert
        inverse = jnp.argsort(order)              # where each pick went
        sizes = jnp.sum(group[:, None] == jnp.arange(count), axis=0,
                        dtype=jnp.int32)
        order, inverse, sizes = map(checkpoint_name, (order, inverse, sizes),
                                    ROUTING_RESIDUAL_NAMES[1:])
    with jax.named_scope(timeline.SCOPE_MOE_COMBINE):
        weight = jnp.where(here, gates, 0.0).astype(h.dtype)

    y = _chunked_experts(
        top_k, chunk_rows(n * top_k, count, total,
                          x.shape[-2] * top_k if x.ndim > 1 else None),
        activation, h,
        jax.tree_util.tree_map(lambda w: w.astype(h.dtype), experts),
        weight, order, inverse, sizes, here)

    if "shared" in params:
        with jax.named_scope(timeline.SCOPE_MOE_SHARED):
            shared = swiglu(params["shared"], h)
            if "shared_gate" in params:
                shared = jax.nn.sigmoid(h @ params["shared_gate"]) * shared
            y = y + shared
    return y.reshape(*lead, d), sizes
