"""``models/routed_decoder.py``: the skeleton of the five routed models, on
TOY halves (CPU, a few hundred parameters, every case a second or two).

Two kinds of halves.  The LIVE ones are a model in small: an attention half
that mixes a sequence's tokens causally and tags one value by name, a routed
half that sends a token to one of four experts and tags its picks, a dense
half.
The ARITHMETIC ones add and scale, so that what the skeleton hands a half
(which tensor, cut how, scaled by what) can be written out in closed form.
What a whole model's tests held of the skeleton before (PRs 36, 40, 43)
is held here without a model.
"""
import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

from autodist_tpu.models.base import rms_norm
from autodist_tpu.models.routed_decoder import (
    equations,
    named_bytes,
    routed_decoder,
)
from autodist_tpu.telemetry import registry

import _routed_cases as routed_cases

VOCAB, D, T, ROWS, EXPERTS, LAYERS = 31, 16, 8, 2, 4, 2
KEPT = MIXED, CHOSEN = "toy/mixed", "toy/chosen"
tokens = functools.partial(routed_cases.tokens, rows=ROWS, t=T, vocab=VOCAB)


def init_of(dense=(), tied=False, final="scale", wide_mix=False):
    """``init(rng)``: the leaves the live halves read, drawn by numpy
    from the key's last word (a compiled ``jax.random`` costs a toy more
    than its cases); ``wide_mix``: the mixing matrix stored twice as wide
    as a half multiplies by."""
    def init(rng):
        draw = np.random.RandomState(int(jax.random.key_data(rng)[-1]))

        def normal(*shape):
            return jnp.asarray(0.3 * draw.standard_normal(shape), jnp.float32)

        params = {"embed": normal(VOCAB, D),
                  "ln_final": {final: jnp.ones((D,)) if final == "scale"
                               else jnp.zeros((D,))}}
        if not tied:
            params["head"] = normal(VOCAB, D)
        for i in range(LAYERS):
            lp = {"mix": normal(D, 2 * D if wide_mix else D),
                  "shift": normal(D)}
            if i in dense:
                lp["ffn"] = {"w_in": normal(D, 2 * D),
                             "w_out": normal(2 * D, D)}
            else:
                lp["moe"] = {"router": 8.0 * normal(D, EXPERTS),
                             "experts": normal(EXPERTS, D, D)}
            params[f"layers_{i}"] = lp
        return params

    return init


def mixing_half(lp, x):
    """``x [1, T, D]`` plus a causal mean of its mixed tokens, tagged."""
    mixed = checkpoint_name(jnp.tanh(x @ lp["mix"] + lp["shift"]), MIXED)
    return x + jnp.cumsum(mixed, axis=1) / jnp.arange(
        1, x.shape[1] + 1)[None, :, None]


def routed_half(lp, parts):
    """``parts [slices, slice, D]`` plus, for every token, the one expert
    its router scores highest, weighted by that score; the picks tagged."""
    scores = jax.nn.softmax(parts @ lp["moe"]["router"])
    chosen = checkpoint_name(jnp.argmax(scores, axis=-1), CHOSEN)
    gates = jax.nn.one_hot(chosen, EXPERTS) * scores
    each = jnp.tanh(jnp.einsum("nsd,edf->nsef", parts, lp["moe"]["experts"]))
    return parts + jnp.einsum("nse,nsef->nsf", gates, each), jnp.sum(
        chosen.reshape(-1, 1) == jnp.arange(EXPERTS), axis=0)


def dense_half(lp, part):
    return part + jnp.tanh(part @ lp["ffn"]["w_in"]) @ lp["ffn"]["w_out"]


def toy(halves_of=lambda i: (mixing_half, routed_half), init=None, **kw):
    kw = {**dict(name="toy", kept_names=KEPT,
                 vocab_size=VOCAB,
                 num_layers=LAYERS, seq_len=T, moe_slice=T, top_k=1,
                 num_experts=EXPERTS, rms_eps=1e-6, xent_chunk=None,
                 remat="full", return_counts=False, config={"toy": True}),
          **kw}
    return routed_decoder(
        init=init or init_of(kw.get("dense_layers", ()),
                             kw.get("tie_head", False)),
        halves_of=halves_of, **kw)


def with_dense_lead(**kw):
    lead, rest = (mixing_half, dense_half), (mixing_half, routed_half)
    return toy(lambda i: lead if i == 0 else rest, dense_layers=(0,), **kw)


def kept_gauges():
    return {m.labels["name"]: m.value
            for m in registry.DEFAULT_REGISTRY.metrics()
            if m.name == "autodist_remat_kept_bytes_per_step"
            and m.labels["name"] in KEPT}


# ---------------------------------------------------------------------------
# the checkpoints
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("moe_slice,maps", [(T, 1), (4, 2)])
def test_full_remat_equals_none_in_loss_and_every_gradient(moe_slice, maps):
    """Both kinds of layer (a dense lead, an expert layer): recomputing
    the halves moves no leaf by more than the order of a float32 sum.  The
    dense lead's FFN runs in the attention's map and under its checkpoint
    where a slice is a sequence, in a map and a checkpoint of its own where
    slices are shorter; the expert layer has one map and two checkpoints."""
    out = {}
    for remat in ("none", "full"):
        spec = with_dense_lead(remat=remat, moe_slice=moe_slice)
        params = spec.init(jax.random.key(1))
        out[remat] = jax.jit(jax.value_and_grad(spec.loss_fn))(
            params, {"tokens": tokens(2)})
    (loss, grads), (want, want_grads) = out["full"], out["none"]
    assert abs(float(loss) - float(want)) < 1e-6
    got = routed_cases.assert_every_gradient_matches(
        grads, want_grads, 1e-5, lambda name: False)
    assert len(got) == 3 + 2 + 2 + 2 + 2
    forward = collections.Counter(
        eqn.primitive.name for eqn in equations(jax.make_jaxpr(
            spec.loss_fn)(params, {"tokens": tokens(2)}).jaxpr))
    assert (forward["scan"], forward["remat2"]) == (maps + 1, maps + 2)


@pytest.mark.parametrize("remat,operands", [
    ("full", False), ("full", True), ("none", False)])
def test_kept_bytes_read_the_tagged_shapes(remat, operands):
    """``autodist_remat_kept_bytes_per_step{name}``: the attention half's
    tagged shape times sequences and layers, the expert half's once a
    layer; through ``operands_of`` where the half cannot multiply by the
    stored leaf at all (a ``[D, 2D]`` matrix cut to ``[D, D]``); nothing
    without a checkpoint."""
    registry.reset_for_testing()
    cut = dict(operands_of=lambda lp: dict(lp, mix=lp["mix"][:, :D]),
               init=init_of(wide_mix=True)) if operands else {}
    spec = toy(remat=remat, **cut)
    jax.eval_shape(spec.loss_fn, spec.init(jax.random.key(0)),
                   {"tokens": tokens(0)})
    # float32 ``[1, T, D]`` a sequence and layer, int32 ``[slices, slice]``
    # a layer
    want = {MIXED: LAYERS * ROWS * T * D * 4, CHOSEN: LAYERS * ROWS * T * 4}
    assert kept_gauges() == (want if remat == "full"
                             else dict.fromkeys(KEPT, 0))
    registry.reset_for_testing()


def test_named_bytes_reads_a_custom_vjps_forward_rule():
    """A value tagged inside a custom VJP's forward rule alone (as the
    flash kernel tags ``o`` and ``lse``) is found, its bytes summed over
    the calls; a plain call of the function tags nothing."""
    @jax.custom_vjp
    def twice(x):
        return 2.0 * x

    twice.defvjp(lambda x: (checkpoint_name(2.0 * x, "toy/twice"), None),
                 lambda _, g: (2.0 * g,))
    x = jax.ShapeDtypeStruct((3, 5), jnp.float32)
    assert named_bytes(lambda x: twice(twice(x)), x) == {
        "toy/twice": 2 * 3 * 5 * 4}
    assert not [e for e in equations(jax.make_jaxpr(twice)(x).jaxpr)
                if e.primitive.name == "name"]


@pytest.mark.parametrize("remat", ["none", "full"])
def test_operands_of_is_traced_once_a_layer_outside_the_mapped_body(remat):
    """``operands_of`` reverses a leaf: the gradient's jaxpr holds one
    ``rev`` a layer forward and one backward (the cotangent's way back to
    the stored leaf), NONE of them under the map over sequences or inside
    a checkpoint's recomputation, dense layer and expert layers alike."""
    spec = with_dense_lead(
        remat=remat, operands_of=lambda lp: dict(lp, mix=lp["mix"][::-1]))
    params = spec.init(jax.random.key(0))
    jaxpr = jax.make_jaxpr(jax.grad(spec.loss_fn))(
        params, {"tokens": tokens(0)}).jaxpr

    def walk(jaxpr, inside=()):
        for eqn in jaxpr.eqns:
            yield eqn, inside
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(inner, inside + (eqn.primitive.name,))

    found = [inside for eqn, inside in walk(jaxpr)
             if eqn.primitive.name == "rev"]
    assert len(found) == 2 * LAYERS
    assert not any({"scan", "while", "remat2"} & set(inside)
                   for inside in found), found


# ---------------------------------------------------------------------------
# what a half is handed
# ---------------------------------------------------------------------------

def seen_by(build, **kw):
    """The shapes every call of each half is traced with."""
    seen = {"attention": [], "second": []}

    def attention(lp, x):
        seen["attention"].append(x.shape)
        return mixing_half(lp, x)

    def routed(lp, parts, *entered):
        seen["second"].append(tuple(p.shape for p in (parts,) + entered))
        return routed_half(lp, parts)

    def dense(lp, part):
        seen["second"].append(part.shape)
        return dense_half(lp, part)

    spec = build(attention, routed, dense, **kw)
    out = jax.eval_shape(spec.loss_fn,
                         spec.init(jax.random.key(0)),
                         {"tokens": tokens(0)})
    return seen, out


def test_dense_layers_give_no_counts_and_run_a_slice_at_a_time():
    """Layer 0 dense: its FFN is traced on ``[1, slice, D]`` (one slice
    under a map, as the attention half on one sequence), the two expert
    layers on all four slices at once, and ``tokens_per_expert`` has the
    expert layers' rows alone."""
    def build(attention, routed, dense, **kw):
        lead, rest = (attention, dense), (attention, routed)
        return toy(lambda i: lead if i == 0 else rest, dense_layers=(0,),
                   remat="none", moe_slice=4, return_counts=True)

    seen, (loss, aux) = seen_by(build)
    assert set(seen["attention"]) == {(1, T, D)}
    assert seen["second"][0] == (1, 4, D)
    assert seen["second"][1:] == [((ROWS * T // 4, 4, D),)]
    assert loss.shape == () and aux["tokens_per_expert"].shape == (
        LAYERS - 1, EXPERTS)


@pytest.mark.parametrize("moe_slice,parts", [
    (4, (4, 4, D)), (T, (ROWS, T, D)), (2 * T, (1, 2 * T, D)),
    (5, (1, ROWS * T, D)), (3 * T, (1, ROWS * T, D))])
def test_a_token_count_the_slice_does_not_divide_is_one_slice(moe_slice,
                                                              parts):
    seen, _ = seen_by(lambda attention, routed, dense: toy(
        lambda i: (attention, routed), moe_slice=moe_slice, remat="none"))
    assert set(seen["second"]) == {(parts,)}


def test_router_reads_input_hands_the_layers_input_cut_alike():
    seen, _ = seen_by(lambda attention, routed, dense: toy(
        lambda i: (attention, routed), moe_slice=4, remat="none",
        router_reads_input=True))
    assert set(seen["second"]) == {((4, 4, D), (4, 4, D))}


def arithmetic(**kw):
    """Halves in closed form: attention adds the layer's ``shift``, the
    second half returns its input plus twice what else it was handed."""
    def attention(lp, x):
        return x + lp["shift"]

    def second(lp, parts, *entered):
        return parts + 2.0 * sum(entered), jnp.zeros((EXPERTS,), jnp.int32)

    return toy(lambda i: (attention, second), remat="none", **kw)


def features_written_out(params, ids, reads_input, embed_scale=1.0):
    x = params["embed"][ids] * embed_scale
    for i in range(LAYERS):
        after = x + params[f"layers_{i}"]["shift"]
        x = after + 2.0 * x if reads_input else after
    return x


@pytest.mark.parametrize("reads_input", [False, True])
def test_the_second_half_is_handed_the_stream_and_the_input(reads_input):
    """``apply_fn``'s logits against the layers written out: with
    ``router_reads_input`` the second half's further operand IS the
    layer's input (before its attention), without it there is none."""
    spec = arithmetic(router_reads_input=reads_input, moe_slice=4)
    params, ids = spec.init(jax.random.key(2)), tokens(4)
    feats = rms_norm(features_written_out(params, ids, reads_input),
                     params["ln_final"]["scale"], 1e-6)
    np.testing.assert_allclose(spec.apply_fn(params, ids),
                               feats @ params["head"].T, rtol=1e-5,
                               atol=1e-6)


def test_embed_scale_multiplies_the_rows_as_they_enter():
    spec = arithmetic(embed_scale=3.0)
    params, ids = spec.init(jax.random.key(2)), tokens(4)
    feats = rms_norm(features_written_out(params, ids, False, 3.0),
                     params["ln_final"]["scale"], 1e-6)
    np.testing.assert_allclose(spec.apply_fn(params, ids),
                               feats @ params["head"].T, rtol=1e-5,
                               atol=1e-6)
    # and it is not the plain model's
    assert not np.allclose(spec.apply_fn(params, ids),
                           arithmetic().apply_fn(params, ids), atol=1e-3)


def test_final_scale_says_what_the_final_norm_multiplies_by():
    """A zero-centred final norm: ``1 + w`` of a leaf ``w`` of zeros is
    the plain model's scale of ones, and a leaf of ones doubles the
    logits."""
    centred = arithmetic(final_scale=lambda p: 1.0 + p["w"],
                         init=init_of(final="w"))
    params, ids = centred.init(jax.random.key(2)), tokens(4)
    plain = dict(params, ln_final={"scale": jnp.ones((D,))})
    want = arithmetic().apply_fn(plain, ids)
    np.testing.assert_allclose(centred.apply_fn(params, ids), want,
                               rtol=1e-6)
    np.testing.assert_allclose(centred.apply_fn(dict(
        params, ln_final={"w": jnp.ones((D,))}), ids), 2.0 * want, rtol=1e-6)


def test_tie_head_has_no_head_leaf_and_no_sparse_vars():
    """Tied, the head multiplies by the table: no ``head`` leaf is read,
    the table's gradient is dense (every row takes one, looked up or not)
    and ``sparse_vars`` is empty; untied, the table is sparse."""
    tied, untied = arithmetic(tie_head=True), arithmetic()
    params = tied.init(jax.random.key(3))
    assert "head" not in params and "head" in untied.init(jax.random.key(3))
    assert tied.sparse_vars == () and untied.sparse_vars == ("embed",)
    assert tied.expert_vars == untied.expert_vars == ("*/moe/experts/*",)
    ids = np.zeros((ROWS, T), np.int32)           # row 0 alone is looked up
    both = jax.jit(jax.grad(tied.loss_fn))(params, {"tokens": ids})["embed"]
    assert np.asarray(both).any(axis=1).all()
    looked_up = jax.jit(jax.grad(untied.loss_fn))(
        dict(params, head=params["embed"]), {"tokens": ids})
    assert not np.asarray(looked_up["embed"])[1:].any()
    np.testing.assert_allclose(both, looked_up["embed"] + looked_up["head"],
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(
        tied.apply_fn(params, ids),
        untied.apply_fn(dict(params, head=params["embed"]), ids), rtol=1e-6)


def test_return_counts_hands_back_every_layers_tokens_per_expert():
    """Every token's pick is counted, a row an expert layer; without
    ``return_counts`` the loss alone."""
    spec = toy(return_counts=True)
    params, batch = spec.init(jax.random.key(4)), {"tokens": tokens(5)}
    loss, aux = jax.jit(spec.loss_fn)(params, batch)
    counts = np.asarray(aux["tokens_per_expert"])
    assert counts.shape == (LAYERS, EXPERTS)
    assert (counts.sum(axis=1) == ROWS * T).all()
    alone = jax.jit(toy().loss_fn)(params, batch)
    assert alone.shape == () and float(alone) == float(loss)


def test_record_attention_is_handed_what_the_halves_noted_once_a_step():
    """An attention half may return ``(x, noted)``: the layers' notes,
    each stacked over the sequences, arrive in ONE call at the top of the
    loss function (None for a layer whose half noted nothing)."""
    def noting(lp, x):
        return mixing_half(lp, x), jnp.max(x)

    noted = []
    spec = toy(lambda i: (noting if i else mixing_half, routed_half),
               record_attention=lambda layers: noted.append(
                   [None if n is None else n.shape for n in layers]))
    jax.eval_shape(spec.loss_fn, spec.init(jax.random.key(0)),
                   {"tokens": tokens(0)})
    assert noted == [[None, (ROWS,)]]


@pytest.mark.parametrize("tied", [False, True])
def test_the_chunked_loss_is_the_plain_one(tied):
    params, batch = arithmetic(tie_head=tied).init(jax.random.key(1)), {
        "tokens": tokens(3)}
    plain, chunked = (jax.jit(arithmetic(
        tie_head=tied, xent_chunk=c).loss_fn)(params, batch)
        for c in (None, 4))
    assert abs(float(plain) - float(chunked)) < 1e-6
    logits = arithmetic(tie_head=tied).apply_fn(params, batch["tokens"])
    picked = jnp.take_along_axis(jax.nn.log_softmax(logits[:, :-1]),
                                 batch["tokens"][:, 1:, None], axis=-1)
    assert abs(float(plain) + float(picked.mean())) < 1e-6


def test_the_rest_is_passed_through():
    spec = toy()
    batch = spec.make_batch(np.random.RandomState(0), 5)
    assert batch["tokens"].shape == (5, T) and batch["tokens"].dtype \
        == np.int32
    assert 0 <= batch["tokens"].min() and batch["tokens"].max() < VOCAB
    assert (spec.name, spec.config) == ("toy", {"toy": True})
