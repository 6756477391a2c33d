"""What the routed decoders share: the skeleton, and what it reads a trace by.

``routed_decoder`` is the decoder every model with ``parallel/moe.py``'s
routed experts is built on (``mla_moe_lm``, ``gqa_dsa_moe_lm``,
``swa_moe_lm``, ``gdn_moe_lm``, ``sconv_moe_lm``): a model file states its
leaves, its mixers and its expert half, and nothing of the embedding, the
layers' maps and checkpoints, the slices, the loss, the gauges or the batch.
``named_bytes`` and ``equations`` read a trace for what a checkpoint keeps
by name.  Nothing here imports a model.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from autodist_tpu.models.base import ModelSpec, cross_entropy_loss, rms_norm
from autodist_tpu.parallel.moe import record_row_budgets
from autodist_tpu.telemetry import registry, step_values, timeline

#: what makes the policy of the layers' checkpoints (a name of this module:
#: a test that counts what a bare checkpoint recomputes patches it here)
save_only_these_names = jax.checkpoint_policies.save_only_these_names


def equations(jaxpr):
    """Every equation of a jaxpr, those of its inner jaxprs included; a
    kernel's own body is the kernel's and is left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(inner)


def named_bytes(fn: Callable, *args) -> dict:
    """``{name: bytes}`` of the values one differentiated call of ``fn``
    tags with ``checkpoint_name`` (``args``: arrays or shapes).  Read off
    the trace of a JVP: a custom VJP tags inside its forward rule, which a
    plain call never runs."""
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
    found = {}
    for eqn in equations(
            jax.make_jaxpr(lambda *a: jax.jvp(fn, a, a))(*shapes).jaxpr):
        if eqn.primitive.name == "name":
            aval = eqn.outvars[0].aval
            found[eqn.params["name"]] = found.get(
                eqn.params["name"], 0) + aval.size * aval.dtype.itemsize
    return found


class Objective(NamedTuple):
    """What a batch asks of the layers, and what the loss is: the ONE seam
    between the skeleton and a training objective.

    ``rows(batch) -> (ids [B, R], carried)``: the table's rows that go
    through the layers for a batch, ``R`` a sequence (at which positions is
    the mixers' to know: a model builds its halves and its objective
    together), and whatever ``loss`` needs besides the features.
    ``loss(feats [B, R, D], head [V, D], carried) -> scalar``, traced
    under ``lm/head_loss``: the final norm's rows of ALL of ``ids`` (the
    norm is a row's own, so an objective that reads some of them cuts
    them out itself)."""
    rows: Callable[[dict], Tuple[jax.Array, Any]]
    loss: Callable[[jax.Array, jax.Array, Any], jax.Array]


def next_token(xent_chunk: Optional[int]) -> Objective:
    """Causal next-token prediction: the batch's tokens go through the
    layers as they are, and row ``t`` is asked for token ``t + 1``."""
    def loss(feats, head, tokens):
        if xent_chunk:
            from autodist_tpu.ops.chunked_xent import \
                chunked_softmax_cross_entropy

            return chunked_softmax_cross_entropy(
                feats[:, :-1], head, tokens[:, 1:], chunk=xent_chunk)
        logits = jnp.einsum("btd,vd->btv", feats, head)
        return cross_entropy_loss(logits[:, :-1], tokens[:, 1:])

    return Objective(rows=lambda batch: (batch["tokens"], batch["tokens"]),
                     loss=loss)


def routed_decoder(*, name: str, init: Callable, halves_of: Callable,
                   kept_names: Tuple[str, ...], vocab_size: int,
                   num_layers: int, seq_len: int, moe_slice: int,
                   top_k: int, num_experts: int, rms_eps: float,
                   xent_chunk: Optional[int], remat: str,
                   return_counts: bool, config: dict,
                   router_reads_input: bool = False,
                   embed_scale: float = 1.0,
                   final_scale: Callable = lambda p: p["scale"],
                   dense_layers: Tuple[int, ...] = (),
                   tie_head: bool = False,
                   record_attention: Optional[Callable] = None,
                   operands_of: Callable = lambda lp: lp,
                   set_pairs_gauges: Callable = lambda tokens: None,
                   objective: Optional[Objective] = None) -> ModelSpec:
    """What the five routed decoders share: the embedding, ``num_layers``
    layers of an attention half (one sequence at a time) and an expert
    half (all of the step's tokens at once, as slices of ``moe_slice``),
    each under its own checkpoint that keeps ``kept_names`` (``remat``:
    "none" | "full"), the final norm, the (chunked) loss, the gauges of what
    the checkpoints keep and of the row budgets, and the batch.
    ``halves_of(i)``: layer ``i``'s ``(attention_half(lp, x [1, T, D]) ->
    [1, T, D], expert_half(lp, parts [slices, slice, D]) -> (parts,
    tokens_per_expert))``, the same functions for layers of one kind.
    ``router_reads_input``: the expert half is also handed the layer's
    INPUT, cut alike (``expert_half(lp, parts, input_parts)``: a router
    placed before attention).  ``embed_scale``: the stream
    enters layer 0 as this times the table's rows.  ``final_scale(params[
    "ln_final"])``: what the final norm multiplies by (a zero-centred
    norm's ``1 + w``).  ``dense_layers``: the layers whose second half is
    a DENSE FFN and no expert half: ``halves_of(i)[1]`` is then
    ``ffn_half(lp, part [1, slice, D]) -> [1, slice, D]``, run one slice
    at a time under a map like the attention half (in that half's own map
    and checkpoint where a slice is a sequence), and the layer has no
    ``tokens_per_expert``.  ``tie_head``: the head multiplies by
    ``params["embed"]`` and there is no ``params["head"]`` (the table's
    gradient is then dense).  ``set_pairs_gauges(tokens)``: the model's
    own gauges, set while tracing.  ``record_attention(noted)``: an
    attention half may return ``(x, noted)``; the layers' ``noted`` (each
    stacked over the sequences, None where a half noted nothing) are handed
    over once a step at the top level of the loss function, where a step
    value can be emitted (``telemetry/step_values.py``).
    ``operands_of(lp)``: a layer's leaves as its halves are handed them,
    formed ONCE a layer outside the maps and the checkpoints (weight-sized
    work, such as cutting a weight for its products: under the map its
    transpose would run once a sequence of the backward).
    ``objective``: the training objective (:class:`Objective`); None is
    :func:`next_token` over ``xent_chunk``.  ``set_pairs_gauges``, the row
    budgets and ``apply_fn`` are handed the ids that go through the
    layers."""
    if remat not in ("none", "full"):
        raise ValueError(f"remat={remat!r}: expected 'none' or 'full'")
    keep = save_only_these_names(*kept_names)
    objective = objective or next_token(xent_chunk)

    @functools.cache
    def as_run(fn, mapped):
        """``fn`` under the layers' checkpoint (under ``lax.map`` no CSE
        barrier is needed)."""
        return fn if remat == "none" else jax.checkpoint(
            fn, policy=keep, prevent_cse=not mapped)

    @functools.cache
    def in_one(attention_half, ffn_half):
        """A dense layer's halves as ONE function of a sequence."""
        def both(lp, x):
            out = attention_half(lp, x)
            return (ffn_half(lp, out[0]), out[1]) if isinstance(out, tuple) \
                else ffn_half(lp, out)

        return both

    def slices(x):
        """``[B, T, D]`` as ``[n, moe_slice, D]``."""
        tokens = x.shape[0] * x.shape[1]
        size = moe_slice if tokens % moe_slice == 0 else tokens
        return x.reshape(tokens // size, size, x.shape[-1])

    def kept_bytes(params, x):
        """What the layers' checkpoints hold by name over a step of ``x
        [B, T, D]``: the tagged shapes of one sequence's attention half
        and of the step's expert half, times how many of each, over the
        layers (one trace a kind of layer)."""
        total = dict.fromkeys(kept_names, 0)
        if remat == "none":
            return total
        parts, found = slices(x), {}
        for i in range(num_layers):
            halves = halves_of(i)
            if halves not in found:
                lp = jax.eval_shape(operands_of, params[f"layers_{i}"])
                found[halves] = [
                    (named_bytes(halves[0], lp, x[:1]), x.shape[0]),
                    (named_bytes(halves[1], lp, parts[:1]), parts.shape[0])
                    if i in dense_layers else
                    (named_bytes(halves[1], lp, *[parts] * (
                        1 + router_reads_input)), 1)]
            for name in kept_names:
                total[name] += sum(tagged.get(name, 0) * times
                                   for tagged, times in found[halves])
        return total

    def layer(lp, x, halves, dense=False):
        """``x [B, T, D]`` through one layer: attention one sequence at a
        time, the experts once over all the tokens (a dense FFN one slice
        at a time; where a slice IS a sequence, in the attention's map and
        under its checkpoint: no ``[B, T, D]`` held between the halves, and
        the FFN's weight gradients add up in the map there is).  Returns
        the layer's ``tokens_per_expert`` ``[count]`` beside ``x``, None
        for a dense layer, and what its attention half noted, if
        anything."""
        lp, entered = operands_of(lp), x
        whole = dense and jax.eval_shape(slices, x).shape == x.shape
        first = as_run(in_one(*halves) if whole else halves[0], True)

        def attended(row):
            out = first(lp, row[None])
            return (out[0][0], out[1]) if isinstance(out, tuple) \
                else (out[0], None)

        x, noted = jax.lax.map(attended, x)
        if whole:
            return x, None, noted
        second = as_run(halves[1], dense)
        if dense:
            y = jax.lax.map(lambda part: second(lp, part[None])[0],
                            slices(x))
            return y.reshape(x.shape), None, noted
        y, counts = second(lp, slices(x), *(
            [slices(entered)] if router_reads_input else []))
        return y.reshape(x.shape), counts, noted

    def set_gauges(params, tokens, x):
        set_pairs_gauges(tokens)
        for kept, held_bytes in kept_bytes(params, x).items():
            registry.gauge(
                "autodist_remat_kept_bytes_per_step",
                "bytes the layers' checkpoints keep from forward to "
                "backward instead of recomputing, by the value's name",
                {"name": kept}).set(held_bytes)

    def features(params, tokens):
        """Final-norm activations ``[B, T, D]`` of the rows ``tokens`` and
        the layers' ``tokens_per_expert`` ``[layers, count]``."""
        with jax.named_scope(timeline.SCOPE_LM_EMBED):
            x = jnp.take(params["embed"], tokens, axis=0)
            if embed_scale != 1.0:
                x = x * embed_scale
        set_gauges(params, tokens, x)
        counts, noted = [], []
        with jax.named_scope(timeline.SCOPE_LM_LAYERS):
            for i in range(num_layers):
                dense = i in dense_layers
                x, c, n = layer(params[f"layers_{i}"], x, halves_of(i),
                                dense)
                noted.append(n)
                if not dense:
                    counts.append(c)
            # here, outside the layers' checkpoints
            record_row_budgets(jnp.stack(counts), tokens.size * top_k,
                               num_experts, slices(x).shape[1] * top_k)
            if record_attention is not None:
                record_attention(noted)
        with jax.named_scope(timeline.SCOPE_LM_HEAD_LOSS):
            feats = rms_norm(x, final_scale(params["ln_final"]), rms_eps)
        return feats, counts

    head_name = "embed" if tie_head else "head"

    def apply_fn(params, tokens):
        feats = features(params, tokens)[0]
        with jax.named_scope(timeline.SCOPE_LM_HEAD_LOSS):
            return jnp.einsum("btd,vd->btv", feats, params[head_name])

    def loss_fn(params, batch):
        ids, carried = objective.rows(batch)
        feats, counts = features(params, ids)
        with jax.named_scope(timeline.SCOPE_LM_HEAD_LOSS):
            loss = objective.loss(feats, params[head_name], carried)
        if return_counts:
            return loss, {"tokens_per_expert": jnp.stack(counts)}
        return loss

    def make_batch(rng: np.random.RandomState, batch_size: int):
        return {"tokens": rng.randint(
            0, vocab_size, (batch_size, seq_len)).astype(np.int32)}

    return ModelSpec(
        name=name,
        init=init, loss_fn=step_values.reporting(loss_fn), apply_fn=apply_fn,
        make_batch=make_batch,
        sparse_vars=() if tie_head else ("embed",),
        expert_vars=("*/moe/experts/*",),
        config=config,
    )
