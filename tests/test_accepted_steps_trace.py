"""The accepted configurations' steps trace what they traced when recorded.

sha256 of the jaxpr text of value and gradient of each accepted
configuration's loss at its cell's own size (abstract shapes: nothing is
computed), with the kernels in their compiled form and object addresses
stripped.  gpt2-medium's hash was taken on commit ``c059ab2`` (PR 34) and
has held since: PR 36's options (``flash_attention(window=)``,
``routed_moe_ffn(router_input=, activation=)``, the decoder skeleton cut
out of ``gqa_dsa_moe_lm``) and PR 37's return of the routed layer's rows
left alone change NOTHING of it, to the character.  The three expert
models' were recorded anew by PR 37, ON PURPOSE: their routed layers bring
the sorted rows back to token order by ``ops/rows_to_tokens.py`` (nine
more kernels in kanana's and keye's text; smallthinker's is that PR's
first record).  A PR that changes one of these models' traces on purpose
records the new hash here and says so in ``CHANGES.md``.
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
        4, 4096, 446819, 16,
        "ac41e22ef4ed81700035667b44186b20bbddccd628568a0eaab596dd30210d14"),
    "keye-vl-2.0-30b-a3b.ep8-share": (
        1, 16384, 1152964, 14,
        "2ddd2fca1e3eeea4c68ccda838d65ac3a3d2e9f6c3ee9751c8bb48c5f27dbb11"),
    "smallthinker-21b-a3b.ep8-share": (
        1, 16384, 383594, 15,
        "c220915a842201c103ab03a2288d3d1816eddfd0d17e31abb5b07c434a70ff71"),
}


def kernel(q, k, v, causal, **kw):
    return flash_attention(q, k, v, causal, interpret=False, **kw)


@pytest.mark.parametrize("name", sorted(TRACES))
def test_value_and_gradient_trace_to_the_recorded_text(name, monkeypatch):
    from autodist_tpu.ops import rows_to_tokens

    monkeypatch.setattr(rows_to_tokens, "_use_interpret", lambda: False)
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
    got = (len(text), text.count("pallas_call"),
           hashlib.sha256(text.encode()).hexdigest())
    assert got == (characters, kernels, digest)
