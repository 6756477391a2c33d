"""The accepted configurations' steps trace what they traced when recorded.

sha256 of the jaxpr text of value and gradient of each accepted
configuration's loss at its cell's own size (abstract shapes: nothing is
computed), with the kernels in their compiled form and object addresses
stripped.  gpt2-medium's hash was taken on commit ``c059ab2`` (PR 34) and
has held since: PR 36's options (``flash_attention(window=)``,
``routed_moe_ffn(router_input=, activation=)``, the decoder skeleton cut
out of ``gqa_dsa_moe_lm``) and PR 37's return of the routed layer's rows
left alone and PR 39's one call of it a layer change NOTHING of it, to
the character.  The three expert models' were recorded anew by PR 37
(their routed layers bring the sorted rows back to token order by
``ops/rows_to_tokens.py``) and again by PR 39, ON PURPOSE: the expert half
of a layer is one call over all of a step's tokens outside the map, the
ladder's three branches a direction are a first chunk and a loop's body
(a fifth to a quarter less text, one ``pallas_call`` less in each), and
what is one number a pick moves by sorts and comparisons.  PR 40 (the
Qwen3-Next share: ``routed_moe_ffn``'s ``shared_gate`` leaf,
``routed_decoder``'s ``final_scale``, four scopes) changed NONE of the
four and recorded its own model's; PR 42 recorded that one anew, ON
PURPOSE (the gated delta rule's backward written out, its scan a second
kernel, segments of 8 chunks: 55,189 characters and two ``pallas_call``
texts more), and left the four others alone.  PR 43 (the LFM2-8B-A1B share:
``routed_decoder``'s ``dense_layers`` and ``tie_head``,
``routed_moe_ffn``'s ``norm_eps``, three scopes) changed NONE of the five
and recorded its own model's.  PR 44 recorded the five expert models'
anew, ON PURPOSE (the routed layer's grouped products are the kernels of
``ops/grouped_matmul.py`` as a TPU traces them, their bodies and the
integers of their visits in the text, each kind a jitted function that
is traced once a shape: 24 calls an expert layer's gradient in
``jax.lax.ragged_dot``'s place), and left gpt2-medium's alone.  PR 46
(the skeleton in ``models/routed_decoder.py``, ``mla_moe_lm`` its fifth
caller) recorded kanana's anew, ON PURPOSE, and changed NONE of the five
others: the text is the parent's but for ONE equation that nothing reads
(127 characters: a second ``slice`` of the embedded batch, by which the
skeleton's ``kept_bytes`` asks for the shapes a dense FFN tags; 44
``pallas_call`` as before).  Kanana's leading dense layer runs both its
halves under the attention's map and checkpoint, as it did, because there
a slice is a sequence.  PR 47 (the SDAR-30B-A3B-Chat share:
``routed_decoder``'s ``objective`` seam with causal next-token as its
default, ``flash_attention(block_diffusion=)``, ``chunked_xent``'s
``weights``, ``rotary_halves``' ``positions``, two scopes) changed NONE of
the six and recorded its own model's.  PR 48 recorded the Qwen3-Next
share's anew, ON PURPOSE, and left the six others alone: a linear layer's
split of ``qkvz``, its convolution (a pad and four shifted slices a
tensor), silu and l2norm are the kernel pair of ``ops/gdn_conv.py`` as a
TPU traces it (``gdn_conv``, ``gdn_conv_bwd``, each kind a jitted function
traced once a shape; z passes through it and the arrays around it are a
head's tokens one after the other): 320,308 characters and five
``pallas_call`` texts more (three of ``gdn_conv``, two of
``gdn_conv_bwd``).  PR 49 recorded the Qwen3-Next share's anew, ON
PURPOSE, and left the six others alone: the gated delta rule's two kernels
form a chunk's state-free blocks in VMEM, a segment a grid step, each a
jitted function traced once a shape, and ``_prepare``, its ``jax.vjp`` and
the ``lax.scan`` over segments are out of the text (two ``pallas_call``
texts fewer, one of ``gdn_scan`` and two of ``gdn_scan_bwd`` are left;
151,553 characters more: the kernels' own lines).  A PR that changes one
of these models' traces on purpose records the new hash here and says so in
``CHANGES.md``.
"""
import functools
import hashlib
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from autodist_tpu.ops.flash_attention import flash_attention
from autodist_tpu.ops.gated_delta_rule import gated_delta_rule

CONFIGS = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                       "benchmark", "configs")
#: configuration -> (rows, tokens a row, characters, kernels, sha256)
TRACES = {
    "gpt2-medium": (
        4, 1024, 869756, 48,
        "33aeee217cc7412cf3b23c92a314f5eaeab4ac537a96f824a79bfda0f11ec301"),
    "kanana-2-30b-a3b.ep8-share": (
        4, 4096, 1026244, 44,
        "4e7caa6d48374c7ad3aa8773802aa0c16436577061aabc023e409cd96a524ef1"),
    "keye-vl-2.0-30b-a3b.ep8-share": (
        1, 16384, 1734266, 42,
        "abf903076b0cd68d460681b4e3020c9aaadea97ea6d800026ad06ef708e42c30"),
    "smallthinker-21b-a3b.ep8-share": (
        1, 16384, 964590, 43,
        "4bdd8a2e2743d69baa536d7845d1f87febdec9ee5fd92dcf45c8e5523c4ab936"),
    "qwen3-next-80b-a3b.ep16-share": (
        2, 8192, 1800732, 50,
        "a8479b110bede9e992c8d8ff31f3b0a364a1b749b6de0b7ef85c228d3c051e1a"),
    "lfm2-8b-a1b.ep4-share": (
        4, 8192, 967221, 42,
        "256f5023c2f3b6830753b48e74da809d5c418afec7f41c62cc5447373465ac50"),
    "sdar-30b-a3b-chat.ep8-share": (
        1, 8192, 978207, 42,
        "eb3872652f613daced00c910569a3f08b3849b8bbd8b06f8ed87649bca8a01be"),
}


def kernel(q, k, v, causal, **kw):
    return flash_attention(q, k, v, causal, interpret=False, **kw)


def compiled_kernels(factory: str, kwargs: dict) -> dict:
    """The factory's kernels in their compiled form: the flash kernel for
    all, and the gated delta rule's kernels for the model that has one."""
    if factory != "gdn_moe_lm":
        return {"attn_fn": kernel}
    return {"attn_fn": kernel, "gdn_fn": functools.partial(
        gated_delta_rule, chunk=kwargs["chunk"], interpret=False)}


@pytest.mark.parametrize("name", sorted(TRACES))
def test_value_and_gradient_trace_to_the_recorded_text(name, monkeypatch):
    from autodist_tpu.ops import gdn_conv, grouped_matmul, rows_to_tokens

    for module in (rows_to_tokens, grouped_matmul, gdn_conv):
        monkeypatch.setattr(module, "_use_interpret", lambda: False)
    rows, t, characters, kernels, digest = TRACES[name]
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        program = json.load(f)["program"]
    module, factory = program["factory"].rsplit(".", 1)
    kwargs = dict(program["kwargs"])
    kwargs["dtype"] = getattr(jnp, kwargs["dtype"])
    spec = getattr(importlib.import_module(module), factory)(
        **kwargs, **compiled_kernels(factory, kwargs))
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct((rows, t), jnp.int32)}
    text = str(jax.make_jaxpr(jax.value_and_grad(spec.loss_fn))(shapes,
                                                                batch))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    got = (len(text), text.count("pallas_call"),
           hashlib.sha256(text.encode()).hexdigest())
    assert got == (characters, kernels, digest)
