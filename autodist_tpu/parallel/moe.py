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
                           dtype=jnp.float32) -> dict:
    """Router over all ``num_experts``, its selection bias (unless
    ``selection_bias`` is off: a softmax router has none), the SwiGLU
    weights of the ``experts_held`` experts that live here (leading axis:
    flag ``*/experts/*`` via ``expert_vars``) and, if ``d_shared``, one
    dense SwiGLU of that width (the shared experts side by side)."""
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
    token's picks may lie here (the top rung of :func:`row_budgets`);
    ``held / total`` of them do if the router spreads evenly.  What a call
    IS handed is the rung its routing takes (:func:`budgets_taken`)."""
    return tokens * top_k, tokens * top_k * held / total


#: rows a tile of the grouped product (``jax.lax.ragged_dot`` on a TPU)
_GROUPED_TILE = 512


def row_budgets(rows: int, held: int, total: int) -> Tuple[int, ...]:
    """The ladder of static row budgets of one call of
    :func:`routed_moe_ffn` over ``rows = tokens * top_k`` picks, from the
    shapes alone: twice and four times the ``held / total`` of them an
    even router sends here, then all of them: at most three rungs,
    ascending, the last always ``rows`` (the budget that holds whatever the
    routing: no capacity).  A rung is a whole number of the grouped
    product's 512-row tiles where ``rows`` is (of 8 rows otherwise).  All
    experts held, or half: ``(rows,)``."""
    tile = _GROUPED_TILE if rows % _GROUPED_TILE == 0 else 8
    rungs = {-(-(factor * rows * held) // (total * tile)) * tile
             for factor in (2, 4)}
    return tuple(sorted(c for c in rungs if c < rows)) + (rows,)


def _rung(rungs, routed):
    """Index of the smallest of ``rungs`` that holds ``routed`` rows
    (any shape of counts)."""
    return jnp.sum(jnp.asarray(routed)[..., None]
                   > jnp.asarray(rungs[:-1], jnp.int32), axis=-1)


def budgets_taken(tokens_per_expert: jax.Array, rows: int, total: int
                  ) -> Tuple[Tuple[int, ...], jax.Array]:
    """``(rungs, calls [len(rungs)] int32)``: the ladder of calls of
    :func:`routed_moe_ffn` over ``rows`` picks each, and how many of the
    calls whose ``tokens_per_expert`` are stacked in ``[..., count]`` took
    each rung.  The same rule the layer applies to the same integers: a
    model sums this over its calls OUTSIDE their checkpoints and maps."""
    rungs = row_budgets(rows, tokens_per_expert.shape[-1], total)
    rung = _rung(rungs, tokens_per_expert.sum(axis=-1)).reshape(-1)
    return rungs, jnp.sum(rung[:, None] == jnp.arange(len(rungs)), axis=0,
                          dtype=jnp.int32)


_ROWS_HELP = ("rows the grouped expert products were handed in the last "
              "step (the row budgets its calls took), and rows an even "
              "router would send here")


def record_row_budgets(tokens_per_expert: jax.Array, rows: int, total: int
                       ) -> None:
    """For a model, once a step, from the top level of its loss function:
    ``tokens_per_expert [..., count]`` of ALL the step's calls of
    :func:`routed_moe_ffn` over ``rows`` picks each, stacked outside their
    checkpoints and maps.  Sets ``autodist_moe_rows_per_step{kind=
    "expected"}`` now, while tracing, and emits the step's calls by rung
    as a step value (``telemetry/step_values.py``: out with the step's
    metrics, no host callback, so the step program stays in the
    persistent compilation cache): after every step a session fetched,
    ``{kind="computed"}`` is the rows of the budgets that step's calls
    took and ``autodist_moe_row_budget_calls_total{rung=<rows>}`` has
    counted them.  The loss function is to be marked ``step_values.
    reporting``."""
    from autodist_tpu.telemetry import registry, step_values

    rungs, calls = budgets_taken(tokens_per_expert, rows, total)
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
                "calls of the routed expert layer by the row budget they "
                "took", {"rung": str(rung)}).inc(taken)

    step_values.emit("moe_row_budget_calls", calls, publish)


def _grouped_swiglu(experts, rows, sizes, activation=jax.nn.silu):
    """:func:`swiglu` of each group of ``rows`` (``sizes`` rows each, in
    order) under its own expert's weights, the gate's ``activation`` the
    caller's.  Rows past the last group come back unwritten."""
    hidden = (activation(jax.lax.ragged_dot(rows, experts["w_gate"], sizes))
              * jax.lax.ragged_dot(rows, experts["w_up"], sizes))
    return jax.lax.ragged_dot(hidden, experts["w_down"], sizes)


def _sorted_rows(budget: int, top_k: int, h, order, sizes):
    """The first ``budget`` places of the sorted order: ``(their tokens'
    rows of h or zeros past the last group [budget, d], which of them hold
    a pick routed here [budget, 1], their picks [budget], their tokens
    [budget])``."""
    index = order[:budget]
    token = index // top_k
    live = (jnp.arange(budget) < sizes.sum())[:, None]
    rows = jnp.where(live, jnp.take(h, token, axis=0), 0)
    return rows, live, index, token


def _experts_on(budget: int, top_k: int, activation, h, experts, weight,
                order, inverse, sizes, here):
    """The held experts' part of the layer on the first ``budget`` rows of
    the sorted order, which hold every pick routed here (``sizes.sum() <=
    budget``), back in token order and summed over the picks: ``[N, d]``.
    The rows that hold a pick are read once, each times its pick's weight,
    and every token is written once (``ops/rows_to_tokens.py``): nothing
    is ``N * top_k`` rows wide, a pick that is not held here is no row at
    all, and what lies past the last group, which a grouped product leaves
    UNWRITTEN, is never read.  A token's terms are added in the order of
    their experts, on every rung."""
    from autodist_tpu.telemetry import timeline

    with jax.named_scope(timeline.SCOPE_MOE_EXPERTS):
        rows, _, index, token = _sorted_rows(budget, top_k, h, order, sizes)
        out = _grouped_swiglu(experts, rows, sizes, activation)
    with jax.named_scope(timeline.SCOPE_MOE_COMBINE):
        return rows_to_tokens(out, token, jnp.take(weight.reshape(-1), index),
                              sizes.sum(), h.shape[0])


def _experts_on_transposed(budget: int, top_k: int, activation, g, h,
                           experts, weight, order, inverse, sizes, here):
    """The cotangents of ``(h, experts, weight)`` under :func:`_experts_on`
    for the cotangent ``g [N, d]`` of its result, on ``budget`` rows too:
    the cotangent of a sorted row is its token's row of ``g`` times its
    pick's weight, a pick's weight takes the dot of its sorted row with its
    token's ``g`` (one number a pick, gathered back), and the cotangent of
    ``h`` is the sorted rows' summed by token as the forward's are."""
    from autodist_tpu.telemetry import timeline

    with jax.named_scope(timeline.SCOPE_MOE_EXPERTS):
        rows, live, index, token = _sorted_rows(budget, top_k, h, order,
                                                sizes)
        out, transpose = jax.vjp(
            lambda experts, rows: _grouped_swiglu(experts, rows, sizes,
                                                  activation),
            experts, rows)
    with jax.named_scope(timeline.SCOPE_MOE_COMBINE):
        g_rows = jnp.take(g, token, axis=0)
        d_out = jnp.where(live, g_rows * jnp.take(
            weight.reshape(-1), index)[:, None], 0)
        dots = jnp.sum(jnp.where(live, out, 0) * g_rows, axis=-1)
        # a pick past the budget is not held here: any place will do
        d_weight = jnp.where(here, jnp.take(dots, jnp.minimum(
            inverse, budget - 1)).reshape(here.shape), 0)
    with jax.named_scope(timeline.SCOPE_MOE_EXPERTS):
        d_experts, d_rows = transpose(d_out)
        d_h = rows_to_tokens(d_rows, token, jnp.ones_like(dots), sizes.sum(),
                             h.shape[0])
    return d_h, d_experts, d_weight


def _switch(rungs, sizes, branch, *operands):
    """``branch(budget)(*operands)`` for the smallest of ``rungs`` that
    holds ``sizes.sum()`` rows, chosen on the device (one rung: no
    ``switch``, the branch itself)."""
    return jax.lax.switch(_rung(rungs, sizes.sum()),
                          [branch(c) for c in rungs], *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _budgeted_experts(top_k: int, rungs, activation, h, experts, weight,
                      order, inverse, sizes, here):
    """:func:`_experts_on` the smallest of ``rungs`` that holds the picks
    routed here.  Differentiated as written, ``switch`` would make every
    branch hand back every branch's residuals, zeros for those not taken:
    each call would write the top rung's as zeros.  So the residuals are
    the INPUTS, which the branches share, and the backward switches on the
    same rung (recomputed from ``sizes``) and runs that branch's forward
    again and its transpose inside it."""
    return _switch(rungs, sizes, lambda c: functools.partial(
        _experts_on, c, top_k, activation), h, experts, weight, order,
        inverse, sizes, here)


def _budgeted_experts_fwd(top_k, rungs, activation, *operands):
    return _budgeted_experts(top_k, rungs, activation, *operands), operands


def _budgeted_experts_bwd(top_k, rungs, activation, operands, g):
    # h, the experts' leaves and the weights; no cotangent for the integers
    sizes = operands[5]
    return _switch(rungs, sizes, lambda c: functools.partial(
        _experts_on_transposed, c, top_k, activation), g, *operands) \
        + (None,) * 4


_budgeted_experts.defvjp(_budgeted_experts_fwd, _budgeted_experts_bwd)


def routed_moe_ffn(params: dict, x: jax.Array, *, top_k: int,
                   experts_held: Optional[Tuple[int, int]] = None,
                   routed_scale: float = 1.0, train_router: bool = True,
                   scoring: str = "sigmoid",
                   router_input: Optional[jax.Array] = None,
                   activation=jax.nn.silu
                   ) -> Tuple[jax.Array, jax.Array]:
    """``k`` of ``E`` routed experts with NO token dropped, for the
    experts this chip holds (DeepSeek-V3's layer, arxiv 2412.19437 §2.1.2,
    ``topk_method`` noaux_tc with one group):

        s = sigmoid(x W_r)          over all E experts, float32, highest
        S = top-k of (s + b)        b: the selection bias, no gradient
        g_e = routed_scale * s_e / sum_{j in S} s_j        for e in S
        y = Shared(x) + sum_{e in S and held} g_e E_e(x)

    ``scoring="softmax"``: ``s = softmax(x W_r)`` over all ``E`` (the
    Qwen3-MoE router with ``norm_topk_prob``); where ``params`` has no
    ``router_bias`` the selection is by the scores alone, and no
    ``shared`` leaves means no shared expert.  ``scoring=
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
    there are any, keep ``silu``.

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
    are sorted by local expert, the picks of absent experts last.  The
    first ``C`` rows of that order are gathered and go through three
    grouped products (``jax.lax.ragged_dot``, on a TPU one Mosaic kernel
    each that leaves the row tiles past the last group alone), then are
    added, each times its pick's weight, to their tokens' rows by one
    kernel that reads those ``C`` rows and writes the ``N`` tokens once
    (``ops/rows_to_tokens.py``; the backward's cotangent of ``x`` alike).
    ``C`` is the smallest rung of :func:`row_budgets` (from the shapes
    alone: twice and four times what an even router sends here, then
    ``N * k``) that holds the picks routed here, chosen ON THE DEVICE from
    ``sizes.sum()`` by one ``jax.lax.switch``, forward and backward alike.
    Whatever the routing, every pick of a held expert is computed: with
    all tokens on the held experts the call takes the top rung and the
    groups fill all ``N * k`` rows.  Nothing but the routing chooses a
    rung.  CALL THIS UNDER ``jax.lax.map``, NOT ``jax.vmap``: batched, a
    ``switch`` becomes a ``select`` and every rung runs.  A budget is a
    call's: a heavy sequence costs its own call one step of the ladder.

    Returns ``(y, tokens_per_expert [count] int32)``;
    :func:`budgets_taken` of the second says which rung the call took.
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
        picked = jnp.take_along_axis(scores, chosen, axis=-1)   # [N, k]
        if scoring == "softmax_of_picked":
            gates = routed_scale * jax.nn.softmax(picked, axis=-1)
        else:
            gates = routed_scale * picked / picked.sum(-1, keepdims=True)
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

    y = _budgeted_experts(
        top_k, row_budgets(n * top_k, count, total), activation, h,
        jax.tree_util.tree_map(lambda w: w.astype(h.dtype), experts),
        weight, order, inverse, sizes, here)

    if "shared" in params:
        with jax.named_scope(timeline.SCOPE_MOE_SHARED):
            y = y + swiglu(params["shared"], h)
    return y.reshape(*lead, d), sizes
