"""Every heavy equation of a train step falls under a scope of
``telemetry/timeline.py``'s ``SCOPE_*`` vocabulary (docs/observability.md):
the step ``AutoDist(AllReduce) -> create_distributed_session`` builds from
each model factory the benchmark's cells run, at tiny widths on the CPU,
walked as a jaxpr (forward, backward and optimizer; into ``scan``,
``cond``, ``checkpoint`` and ``custom_vjp`` bodies).  What the benchmark's
reader (``benchmark/step_scopes.py``) leaves ``unscoped`` on the chip is
what this walk would have found first.
"""
import contextlib
import functools
import re

import jax
import numpy as np
import optax
import pytest

import _routed_cases as routed_cases
from autodist_tpu import strategy as strategies
from autodist_tpu.autodist import (
    AutoDist,
    _reset_default_autodist_for_testing,
)
from autodist_tpu.mesh import build_mesh
from autodist_tpu.models.gdn_moe_lm import gdn_moe_lm
from autodist_tpu.models.gqa_bd_moe_lm import gqa_bd_moe_lm
from autodist_tpu.models.gqa_dsa_moe_lm import gqa_dsa_moe_lm
from autodist_tpu.models.mla_moe_lm import mla_moe_lm
from autodist_tpu.models.sconv_moe_lm import sconv_moe_lm
from autodist_tpu.models.swa_moe_lm import swa_moe_lm
from autodist_tpu.models.transformer_lm import transformer_lm
from autodist_tpu.ops import flash_attention
from autodist_tpu.ops.gated_delta_rule import gated_delta_rule
from autodist_tpu.telemetry import timeline

FLASH = functools.partial(flash_attention, interpret=True, block_q=32,
                          block_k=32)
ROUTED = dict(vocab_size=61, d_model=32, d_expert=12, num_experts=16,
              top_k=3, experts_held=(0, 4), xent_chunk=32,
              train_router=False, attn_fn=FLASH)
#: the seven factories of the benchmark's eight cells, each as its cell
#: runs it (the kernel and not the dense softmax, the chunked loss where
#: the configuration asks for it, checkpoints, maps over sequences)
FACTORIES = {
    "transformer_lm": (transformer_lm, dict(
        vocab_size=61, num_layers=2, num_heads=2, head_dim=16, d_ff=48,
        max_len=64, seq_len=64, attn_fn=FLASH)),
    "mla_moe_lm": (mla_moe_lm, dict(
        ROUTED, num_layers=2, first_dense=1, num_heads=2, qk_nope=8,
        qk_rope=4, v_head=8, kv_lora=16, d_ff=48, shared_experts=2,
        seq_len=64)),
    "gqa_dsa_moe_lm": (gqa_dsa_moe_lm, dict(
        ROUTED, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        index_heads=2, index_dim=8, topk=40, seq_len=96, block_k=32,
        index_rows=32, moe_slice=96)),
    "swa_moe_lm": (swa_moe_lm, dict(
        ROUTED, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        window=40, window_layout=(0, 1), rope_layout=(0, 1), seq_len=96,
        block_k=32, moe_slice=96)),
    "gdn_moe_lm": (gdn_moe_lm, dict(
        ROUTED, num_layers=2, full_interval=2, linear_key_heads=2,
        linear_value_heads=4, linear_head_dim=8, num_heads=4,
        num_kv_heads=2, head_dim=16, rotary_dim=4, d_shared=12, seq_len=64,
        chunk=16, block_k=32, moe_slice=64,
        gdn_fn=functools.partial(gated_delta_rule, chunk=16, segment=2,
                                 interpret=True))),
    "gqa_bd_moe_lm": (gqa_bd_moe_lm, dict(
        ROUTED, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        block_length=4, seq_len=96, block_k=32, moe_slice=96)),
    "sconv_moe_lm": (sconv_moe_lm, dict(
        ROUTED, layer_types=("conv", "full_attention"), num_dense_layers=1,
        num_heads=4, num_kv_heads=2, head_dim=8, d_ff=48, seq_len=64,
        block_k=32, moe_slice=64)),
}
VOCABULARY = {value for name, value in vars(timeline).items()
              if name.startswith("SCOPE_")}
NAMED = re.compile("|".join(rf"\b{re.escape(v)}\b"
                            for v in sorted(VOCABULARY)))
#: the primitives that become a step's long device operations
HEAVY = {"dot_general", "ragged_dot_general", "pallas_call", "gather",
         "scatter-add", "sort", "top_k"}


def session(name, **capture):
    factory, kwargs = FACTORIES[name]
    spec = factory(**kwargs)
    _reset_default_autodist_for_testing()
    ad = AutoDist(strategy_builder=strategies.AllReduce(),
                  mesh_axes={"data": 1})
    with ad.scope():
        ad.capture(params=spec.init(jax.random.key(0)),
                   optimizer=optax.adamw(1e-3), loss_fn=spec.loss_fn,
                   sparse_vars=spec.sparse_vars,
                   expert_vars=spec.expert_vars, **capture)
    sess = ad.create_distributed_session(
        mesh=build_mesh({"data": 1}, devices=jax.devices()[:1]))
    _reset_default_autodist_for_testing()
    return sess, spec.make_batch(np.random.RandomState(0), 2)


def step_arguments(sess, batch):
    return (sess._params, sess._opt_state, sess._sync_state,
            sess._step.place_batch(batch))


def equations(jaxpr, outer=""):
    """``(equation, its whole name stack)``: an inner jaxpr's stacks are
    relative to the equation that holds it, whose primitive stands in the
    path as ``<while>``.  A kernel's body is its own."""
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        yield eqn, stack
        if eqn.primitive.name != "pallas_call":
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(inner,
                                     f"{stack}/<{eqn.primitive.name}>")


@functools.cache
def walked(name):
    """The step as a TPU traces it (the grouped products kernels)."""
    sess, batch = session(name)
    with routed_cases.grouped_products_by_the_kernels():
        jaxpr = sess._step.step_fn.trace(
            *step_arguments(sess, batch)).jaxpr
    return [(eqn.primitive.name, stack, str(eqn.source_info.traceback))
            for eqn, stack in equations(jaxpr.jaxpr)]


@pytest.mark.parametrize("name", sorted(FACTORIES))
def test_every_heavy_equation_is_under_a_scope(name):
    eqns = walked(name)
    heavy = [(prim, stack) for prim, stack, _ in eqns if prim in HEAVY]
    # forward and backward of the layers, the head and the table
    assert {"dot_general", "pallas_call", "gather"} <= {p for p, _ in heavy}
    bare = sorted({(prim, stack) for prim, stack in heavy
                   if not NAMED.search(stack)})
    assert not bare, bare
    # the routed layer's grouped products, forward and both transposes,
    # are kernels under its experts' scope
    grouped = [stack for prim, stack in heavy
               if prim == "pallas_call" and "grouped_" in stack]
    assert all(timeline.SCOPE_MOE_EXPERTS in stack for stack in grouped)
    assert {stack.rsplit("/", 1)[-1] for stack in grouped} == (
        set() if name == "transformer_lm" else
        {"grouped_rows", "grouped_rows_t", "grouped_weights"}), grouped
    assert "ragged_dot_general" not in {p for p, _ in heavy}
    # a body reached through every kind of container the steps have
    stacks = "\n".join(stack for _, stack in heavy)
    # (<while>: the routed layer's loop over further chunks, PR 39; a
    # ``switch`` over row budgets, ``<cond>``, until then)
    for container in ("transpose(jvp(", "<scan>", "<remat2>", "<while>",
                      "<custom_vjp_call>") \
            if name != "transformer_lm" else ("transpose(jvp(",):
        assert container in stacks, container


@pytest.mark.parametrize("name", sorted(FACTORIES))
def test_the_optimizer_is_under_its_scope(name):
    optax_eqns = [stack for _, stack, where in walked(name)
                  if "/optax/" in where]
    assert len(optax_eqns) > 20
    assert all(timeline.SCOPE_STEP_OPTIMIZER in s for s in optax_eqns)
    # and nothing of a model is: the scope closes before the next part
    assert not any(
        timeline.SCOPE_STEP_OPTIMIZER in stack and "jvp" in stack
        for _, stack, _ in walked(name))


def test_every_scope_is_entered_by_a_factory():
    """A constant that no step enters names nothing: delete it."""
    sess, batch = session("transformer_lm", numerics={"clip_norm": 1.0})
    guarded = sess._step.step_fn.trace(*step_arguments(sess, batch)).jaxpr
    seen = "\n".join(
        [stack for name in FACTORIES for _, stack, _ in walked(name)]
        + [stack for _, stack in equations(guarded.jaxpr)])
    assert sorted(v for v in VOCABULARY if v not in seen) == []


def test_scopes_do_not_change_the_lowered_step(monkeypatch):
    """Scopes are metadata: with every ``jax.named_scope`` a null context
    the step lowers to the same StableHLO, locations aside."""
    def lowered():
        sess, batch = session("mla_moe_lm")
        return sess.lower_step(batch).as_text()

    with_scopes = lowered()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = lowered()
    assert "loc(" not in with_scopes
    assert with_scopes == without
