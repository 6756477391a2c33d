"""The accepted configurations' steps trace what they traced at PR 34.

sha256 of the jaxpr text of value and gradient of each accepted
configuration's loss at its cell's own size (abstract shapes: nothing is
computed), with the flash kernel in its compiled form and object addresses
stripped.  The hashes were taken on commit ``c059ab2`` (PR 34) and again on
PR 36's tree, whose options (``flash_attention(window=)``,
``routed_moe_ffn(router_input=, activation=)``, the decoder skeleton cut
out of ``gqa_dsa_moe_lm``) left alone must change NOTHING of these traces,
to the character.  A PR that changes one of these models' traces on
purpose records the new hash here and says so in ``CHANGES.md``.
"""
import hashlib
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from autodist_tpu.ops.flash_attention import flash_attention

CONFIGS = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                       "benchmark", "configs")
#: configuration -> (rows, tokens a row, characters, kernels, sha256)
TRACES = {
    "gpt2-medium": (
        4, 1024, 869756, 48,
        "33aeee217cc7412cf3b23c92a314f5eaeab4ac537a96f824a79bfda0f11ec301"),
    "kanana-2-30b-a3b.ep8-share": (
        4, 4096, 421434, 7,
        "99880f232acf802b3ebec81551864b3a15c3f57d84dd361c85ece34de9a7f627"),
    "keye-vl-2.0-30b-a3b.ep8-share": (
        1, 16384, 1127285, 5,
        "371c658baaf9991f6136c8d36026991f5e1ba9d49cdf02865276c663ba56fc79"),
}


def kernel(q, k, v, causal, **kw):
    return flash_attention(q, k, v, causal, interpret=False, **kw)


@pytest.mark.parametrize("name", sorted(TRACES))
def test_value_and_gradient_trace_to_the_recorded_text(name):
    rows, t, characters, kernels, digest = TRACES[name]
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        program = json.load(f)["program"]
    module, factory = program["factory"].rsplit(".", 1)
    kwargs = dict(program["kwargs"])
    kwargs["dtype"] = getattr(jnp, kwargs["dtype"])
    spec = getattr(importlib.import_module(module), factory)(
        **kwargs, attn_fn=kernel)
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct((rows, t), jnp.int32)}
    text = str(jax.make_jaxpr(jax.value_and_grad(spec.loss_fn))(shapes,
                                                                batch))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert (len(text), text.count("pallas_call")) == (characters, kernels)
    assert hashlib.sha256(text.encode()).hexdigest() == digest
