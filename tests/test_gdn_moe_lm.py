"""``models/gdn_moe_lm.py`` and ``parallel/moe.py``'s ``shared_gate``
against the plain reference ``benchmark/reference/qwen3_next.py`` (CPU, tiny
widths, seeded weights).

A CPU multiplies float32 exactly, so program and reference differ by the
order of their float32 sums alone; ``RTOL`` is 1e-4, five times
``test_swa_moe_lm.py``'s: the gated norm divides a head's eight outputs by
their root mean square, which at these widths is small enough for its
epsilon to count, and its weight's gradient reads 4e-5 apart.  The weights
are the benchmark's (normal(0, 0.02), leaves named ``scale`` 1) with every
matrix times ``GAIN``, so that the routers' scores spread and a part of the
model left out moves the loss.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.models import gdn_moe_lm as model
from autodist_tpu.models.gdn_moe_lm import gdn_moe_lm
from autodist_tpu.ops import gated_delta_rule as gdr
from autodist_tpu.ops.flash_attention import flash_attention
from autodist_tpu.parallel.moe import init_routed_moe_params, routed_moe_ffn
from benchmark.reference import qwen3_next as ref

import _routed_cases as routed_cases

RTOL = 1e-4
GAIN = 8.0
TINY = dict(vocab_size=61, num_layers=4, d_model=32, full_interval=4,
            linear_key_heads=2, linear_value_heads=4, linear_head_dim=8,
            conv_kernel=4, num_heads=4, num_kv_heads=2, head_dim=16,
            rotary_dim=4, d_expert=12, d_shared=12, num_experts=16, top_k=3,
            seq_len=64, chunk=16, block_k=32, moe_slice=64)
FLASH = functools.partial(flash_attention, interpret=True, block_q=32,
                          block_k=32)


def settings(first_held=0, top_k=3, train_router=True, wrong="", rotary=4,
             full_interval=4):
    return ref.Settings(top_k=top_k, first_held=first_held,
                        full_interval=full_interval, rotary=rotary,
                        theta=1e7, eps=1e-6, train_router=train_router,
                        wrong=wrong)


seeded = functools.partial(routed_cases.seeded, gain=GAIN)
tokens = functools.partial(routed_cases.tokens, t=64)
rel = routed_cases.rel


def kernel_scan(*operands):
    return gdr.gated_delta_rule(*operands, chunk=16, segment=2,
                                interpret=True)


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("held,remat,chunk,train_router,attn,scan", [
    (None, "none", None, True, None, None),
    ((4, 4), "full", 32, False, None, None),
    ((4, 4), "full", None, True, FLASH, kernel_scan),
    ((0, 2), "none", 32, False, FLASH, kernel_scan),
])
def test_loss_and_every_gradient_match_the_reference(held, remat, chunk,
                                                     train_router, attn,
                                                     scan):
    spec = gdn_moe_lm(**TINY, experts_held=held, remat=remat,
                      xent_chunk=chunk, train_router=train_router,
                      attn_fn=attn, gdn_fn=scan)
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 3)
    batch = tokens(5)
    s = settings(held[0] if held else 0, train_router=train_router)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(spec.loss_fn))(
            params, {"tokens": batch})
        want, want_grads = ref.loss_and_grads(params, jnp.asarray(batch),
                                              row_block=2, s=s)
    assert abs(float(loss) - float(want)) < RTOL
    routed_cases.assert_every_gradient_matches(
        grads, want_grads, RTOL,
        lambda name: not train_router and name.endswith("router"))


#: another model in the stated one's place: the reference's ``wrong``, or
#: the program's kwargs
WRONG = {"no_decay": {}, "beta_one": {}, "no_conv": {},
         "rotary_all": {}, "rotary_on_eight_columns": dict(rotary_dim=8)}


def stated_weights():
    return seeded(jax.eval_shape(gdn_moe_lm(**TINY).init,
                                 jax.random.key(0)), 3)


@functools.cache
def reference_loss(wrong=""):
    """The reference's loss of the stated model, or of another."""
    with jax.default_matmul_precision("highest"):
        return float(jax.jit(functools.partial(
            ref.sum_loss_fn, s=settings(wrong=wrong)))(
            stated_weights(), jnp.asarray(tokens(5)))) / (2 * 63)


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_another_model_is_another_loss(wrong):
    if not WRONG[wrong]:         # the reference computes the other model
        other = reference_loss(wrong)
    else:
        spec = gdn_moe_lm(**dict(TINY, **WRONG[wrong]), remat="none")
        with jax.default_matmul_precision("highest"):
            other = float(jax.jit(spec.loss_fn)(
                stated_weights(), {"tokens": tokens(5)}))
    assert abs(other - reference_loss()) > 1e-4


def test_layers_are_of_two_kinds_by_index():
    """Layer ``i`` is full where ``(i + 1) % full_interval == 0``: its
    leaves, and which mixer is called."""
    calls = {"attn": 0, "gdn": 0}

    def attn(q, k, v, causal, **kw):
        calls["attn"] += 1
        assert q.shape[2:] == (4, 16) and k.shape[2:] == (2, 16) and not kw
        return FLASH(q, k, v, causal)

    def scan(q, k, v, g, beta):
        calls["gdn"] += 1
        assert q.shape[2:] == (2, 8) and v.shape[2:] == (4, 8)
        assert g.dtype == beta.dtype == jnp.float32 and g.shape[2:] == (4,)
        return gdr.gated_delta_rule(q, k, v, g, beta, chunk=16)

    spec = gdn_moe_lm(**dict(TINY, num_layers=8), remat="none",
                      attn_fn=attn, gdn_fn=scan)
    params = spec.init(jax.random.key(0))
    for i in range(8):
        lp = params[f"layers_{i}"]
        assert ("attn" in lp, "gdn" in lp) == ((i + 1) % 4 == 0,
                                               (i + 1) % 4 != 0)
        assert lp["moe"]["shared_gate"].shape == (32, 1)
    jax.eval_shape(spec.loss_fn, params, {"tokens": tokens(1)})
    assert calls == {"attn": 2, "gdn": 6}
    with pytest.raises(ValueError, match="value heads"):
        gdn_moe_lm(**dict(TINY, linear_value_heads=3))


def test_rotary_touches_the_first_columns_alone():
    """64 of 256 columns at the published widths: positions reach a head
    through its first ``columns`` and leave the rest as they were; at
    position 0 nothing turns."""
    x = jax.random.normal(jax.random.key(0), (1, 32, 16, 256))
    y = model.partial_rotary(x, 1e7, 64)
    np.testing.assert_array_equal(y[..., 64:], x[..., 64:])
    np.testing.assert_allclose(y[:, 0], x[:, 0], atol=1e-6)
    moved = np.abs(np.asarray(y - x))[:, 1:].max(axis=(0, 1, 2)) > 0
    assert moved[:64].all() and not moved[64:].any()
    # halves of those 64 against each other: the reference's rotate_half
    from benchmark.reference.keye_vl2 import _rotary

    np.testing.assert_allclose(
        y[0, ..., :64], _rotary(x[0, ..., :64], 1e7, jnp.arange(32)),
        atol=1e-5)


def test_causal_conv_reads_no_later_token():
    x = jax.random.normal(jax.random.key(0), (1, 12, 3, 5))
    w = jax.random.normal(jax.random.key(1), (3, 5, 4))
    y = model.causal_conv(x, w)
    want = sum(w[..., i] * jnp.pad(x, ((0, 0), (3, 0), (0, 0), (0, 0)))[
        :, i:i + 12] for i in range(4))
    np.testing.assert_allclose(y, want, atol=1e-6)
    later = model.causal_conv(x.at[:, 7:].set(0.0), w)
    np.testing.assert_array_equal(later[:, :7], y[:, :7])
    # token t reads t - 3 .. t: a tap's weight on its own token is w[3]
    np.testing.assert_allclose(
        model.causal_conv(x[:, :1], w)[:, 0], x[:, 0] * w[..., 3],
        atol=1e-6)


def one_layer(full: bool, seed=13):
    kw = dict(TINY, num_layers=1, full_interval=1 if full else 4,
              remat="none")
    spec = gdn_moe_lm(**kw)
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), seed)
    x = jnp.take(params["embed"], tokens(seed + 1, rows=1), axis=0)
    return spec, params, x


@pytest.mark.parametrize("full,gate", [(True, "wq"), (False, "w_qkvz")])
def test_both_mixers_are_gated(full, gate):
    """The full mixer's output passes through ``sigmoid(gate)`` (the second
    half of ``W_q``'s columns a head) and the linear mixer's through
    ``silu(z)`` (the last columns of ``W_qkvz`` a key head): with those
    columns zero the gates read 1/2 and 0."""
    spec, params, x = one_layer(full)
    name = "attn" if full else "gdn"
    lp = params["layers_0"]
    s = settings(full_interval=1 if full else 4)
    with jax.default_matmul_precision("highest"):
        h = ref._rms0(x[0], lp["ln_in"], 1e-6)
        mixer = ref._full_mixer if full else ref._linear_mixer
        want = mixer(h, lp[name], s, None)
        w = lp[name][gate]
        half = w.shape[-1] // 2 if full else 4 * 8      # q | gate, qkv | z
        cut = dict(lp[name], **{gate: w.at[..., half:].set(0.0)})
        gated = mixer(h, cut, s, None)
    assert rel(want, gated) > 1e-2
    if not full:                 # silu(0) = 0: nothing comes out
        assert not np.asarray(gated).any()
    else:                        # sigmoid(0) = 1/2: W_o is linear in it
        with jax.default_matmul_precision("highest"):
            twice = mixer(h, dict(cut, wo=2.0 * lp[name]["wo"]), s, None)
        assert rel(twice, 2.0 * gated) < RTOL


def test_norms_are_zero_centred_but_the_gated_one():
    """``w = 0`` is a scale of one for the layer's, the heads' and the
    final norm; the gated norm of the linear mixer multiplies by its leaf
    itself (a leaf of zeros silences the mixer)."""
    spec, params, x = one_layer(False)
    batch = {"tokens": tokens(2)}
    zeroed = jax.tree.map_with_path(
        lambda path, a: jnp.zeros_like(a) if path[-1].key == "w" else a,
        params)
    ones = jax.tree.map_with_path(
        lambda path, a: jnp.ones_like(a) if path[-1].key == "w" else a,
        params)
    with jax.default_matmul_precision("highest"):
        got = spec.loss_fn(zeroed, batch)
        want = ref.loss_and_grads(zeroed, jnp.asarray(batch["tokens"]),
                                  row_block=2, s=settings())[0]
        doubled = spec.loss_fn(ones, batch)
    assert abs(float(got) - float(want)) < RTOL
    assert abs(float(doubled) - float(got)) > 1e-4     # a scale of two
    lp = params["layers_0"]
    silent = dict(lp, gdn=dict(lp["gdn"], norm={"scale": jnp.zeros(8)}))
    with jax.default_matmul_precision("highest"):
        y = ref._linear_mixer(ref._rms0(x[0], lp["ln_in"], 1e-6),
                              silent["gdn"], settings(), None)
    assert not np.asarray(y).any()


def test_decay_offsets_spread_the_heads():
    a = np.exp(np.asarray(model.decay_offsets(32)))
    np.testing.assert_allclose(a, 16.0 * (np.arange(32) + 0.5) / 32,
                               rtol=1e-6)
    assert a.min() == pytest.approx(0.25) and a.max() == pytest.approx(15.75)
    np.testing.assert_allclose(model.decay_offsets(4), ref._decay_offsets(4))


# ---------------------------------------------------------------------------
# the shared expert's gate, and the shares
# ---------------------------------------------------------------------------

def moe_layer(seed, held=None):
    p = init_routed_moe_params(jax.random.key(seed), 32, 12, 16,
                               experts_held=held, d_shared=12,
                               selection_bias=False, shared_gate=True)
    assert "router_bias" not in p and p["shared_gate"].shape == (32, 1)
    return dict(jax.tree.map(lambda a: a * GAIN, p),
                router=p["router"] * 40.0)


def share_of(params, first, count):
    return dict(params, experts=jax.tree.map(
        lambda a: a[first:first + count], params["experts"]))


def test_shared_experts_output_is_gated_one_number_a_token():
    params = moe_layer(3)
    x = jax.random.normal(jax.random.key(4), (2, 24, 32))
    kw = dict(top_k=4, scoring="softmax_of_picked")
    gated = routed_moe_ffn(params, x, **kw)[0]
    ungated = routed_moe_ffn(
        {k: v for k, v in params.items() if k != "shared_gate"}, x, **kw)[0]
    routed = routed_moe_ffn(
        {k: v for k, v in params.items() if not k.startswith("shared")}, x,
        **kw)[0]
    gate = jax.nn.sigmoid(x @ params["shared_gate"])
    assert gate.shape == (2, 24, 1)
    assert rel(gated - routed, gate * (ungated - routed)) < RTOL
    assert rel(gated, ref.experts(x, params, s=settings(top_k=4))) < RTOL


def test_with_no_experts_held_it_is_the_whole_layer():
    """``experts_held=None``: all 16 experts, the uncut reference's layer."""
    spec, params, x = one_layer(False)
    lp = params["layers_0"]
    with jax.default_matmul_precision("highest"):
        whole = ref._layer(x[0], lp, False, settings(), None)
        stream = x[0] + ref._linear_mixer(
            ref._rms0(x[0], lp["ln_in"], 1e-6), lp["gdn"], settings(), None)
        y = routed_moe_ffn(lp["moe"], ref._rms0(stream, lp["ln_post"], 1e-6),
                           top_k=3, scoring="softmax_of_picked")[0]
    assert lp["moe"]["experts"]["w_gate"].shape[0] == 16
    assert rel(stream + y, whole) < RTOL


def test_sixteen_shares_add_up_to_the_uncut_reference_layer():
    """THE SHARE TEST.  One linear layer of the uncut reference (all 16
    experts of the tiny router) against sixteen chips' parts: each chip's
    ROUTED part (``routed_moe_ffn`` as the model calls it, softmax over all
    16, expert ``c`` held, the shared leaves left out) on the layer's
    normed stream; the mixer, the residual, the router and the gated
    shared expert, which every chip computes alike, counted ONCE.  They
    add up to the reference's layer output."""
    spec, params, x = one_layer(False)
    lp = params["layers_0"]
    routed_leaves = {k: v for k, v in lp["moe"].items()
                     if not k.startswith("shared")}
    with jax.default_matmul_precision("highest"):
        whole = ref._layer(x[0], lp, False, settings(), None)
        stream = x[0] + ref._linear_mixer(
            ref._rms0(x[0], lp["ln_in"], 1e-6), lp["gdn"], settings(), None)
        normed = ref._rms0(stream, lp["ln_post"], 1e-6)
        shared = jax.nn.sigmoid(normed @ lp["moe"]["shared_gate"]) \
            * ref._swiglu(normed, lp["moe"]["shared"], None)
        parts = [routed_moe_ffn(
            share_of(routed_leaves, chip, 1), normed, top_k=3,
            experts_held=(chip, 1), scoring="softmax_of_picked")[0]
            for chip in range(16)]
        one_chip = routed_moe_ffn(share_of(lp["moe"], 5, 1), normed, top_k=3,
                                  experts_held=(5, 1),
                                  scoring="softmax_of_picked")[0]
    assert rel(stream + shared + sum(parts), whole) < RTOL
    # a chip's own call holds the shared expert beside its routed part
    assert rel(one_chip, shared + parts[5]) < RTOL
    # neither part is a rounding error of the layer
    assert rel(whole, stream) > 1e-2 and rel(whole, stream + shared) > 1e-3
    assert sum(bool(np.asarray(p).any()) for p in parts) >= 8


# ---------------------------------------------------------------------------
# what the checkpoints keep, and the gauges
# ---------------------------------------------------------------------------

def test_backward_runs_no_kernel_twice():
    """With the kept names the differentiated step holds each layer's
    forward kernel once (three scans, one flash forward), each scan's
    backward kernel and the flash backward; without remat the same."""
    def count(remat):
        spec = gdn_moe_lm(**TINY, remat=remat, attn_fn=FLASH,
                          gdn_fn=kernel_scan)
        shapes = jax.eval_shape(spec.init, jax.random.key(0))
        jaxpr = jax.make_jaxpr(jax.grad(spec.loss_fn))(
            shapes, {"tokens": tokens(0)})
        return routed_cases.primitives(jaxpr.jaxpr)["pallas_call"]

    # three scans and their backwards, a flash forward and its backward
    assert count("full") == count("none") == 3 + 3 + 2
    assert set(gdr.RESIDUAL_NAMES) < set(model.KEPT_NAMES)


@pytest.mark.parametrize("t", [64, 256])
def test_gauges_say_what_the_chunked_form_costs(t):
    import inspect

    from autodist_tpu.telemetry import registry

    registry.reset_for_testing()
    spec = gdn_moe_lm(**{**TINY, "seq_len": t})
    jax.eval_shape(spec.loss_fn, jax.eval_shape(
        spec.init, jax.random.key(0)), {"tokens": tokens(0, t=t)})
    got = {m.labels["kind"]: m.value
           for m in registry.DEFAULT_REGISTRY.metrics()
           if m.name == "autodist_gdn_flops_per_step"}
    per = gdr.flops_per_token(8, 8, 16)
    times = 2 * t * 4 * 3         # tokens, value heads, linear layers
    assert got == {k: v * times for k, v in per.items()}
    assert got["computed"] / got["recurrence"] >= 1.0
    kept = {m.labels["name"]: m.value
            for m in registry.DEFAULT_REGISTRY.metrics()
            if m.name == "autodist_remat_kept_bytes_per_step"}
    # o [t, 4, 8] float32 a sequence and linear layer; one state a value
    # head and segment of the shipped length (of 64 tokens in chunks of
    # 16 the whole sequence is one, of 256 there are two)
    segment = inspect.signature(
        gdr.gated_delta_rule).parameters["segment"].default
    segments = -(-t // (16 * segment))
    assert segments == {64: 1, 256: 2}[t]
    assert kept["gated_delta_rule/o"] == 3 * 2 * t * 4 * 8 * 4
    assert kept["gated_delta_rule/states"] == 3 * 2 * segments * 4 * 8 * 8 * 4
    registry.reset_for_testing()
