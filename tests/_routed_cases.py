"""What the tests of the five routed models share.

First what every model's comparison with its reference is made of: the
benchmark's seeded weights, a batch of tokens, a tree by its leaves' names
and the comparison of every leaf's gradient with the reference's, each by
its own norm.  Then a routed expert layer
whose load is chosen to the row, for the tests of ``parallel/moe.py``'s
chunks of the sorted order (``test_mla_moe_lm.py`` for the sigmoid router,
``test_gqa_dsa_moe_lm.py`` for the softmax one).

64 tokens pick 2 of 16 experts, experts 4 and 5 are held: 128 picks a call,
an even router sends 16 here, so a chunk is 32 places of the sorted order
and a call takes one to four of them.  A token's first three columns say
its KIND and the router reads nothing else: kind 0 picks experts 4 and 5
(two picks here), kind 1 picks 4 and 9 (one), kind 2 picks 8 and 9 (none).
The other columns are noise, so that the experts' outputs differ from token
to token."""
import collections
import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from autodist_tpu.models.routed_decoder import equations
from autodist_tpu.ops import grouped_matmul
from autodist_tpu.parallel import moe


def seeded(shapes, seed, gain=1.0):
    """``benchmark/weights.py``'s rule (normal(0, 0.02), leaves named
    ``scale`` 1), every matrix times ``gain``."""
    from benchmark import weights

    made = weights.make_weights(shapes, seed)
    return made if gain == 1.0 else jax.tree.map(
        lambda a: a * gain if a.ndim > 1 else a, made)


def tokens(seed, rows=2, t=96, vocab=61, generator=False):
    """A batch ``[rows, t]``; ``generator``: drawn by ``default_rng``
    (kanana's batches since PR 30), not by ``RandomState``."""
    if generator:
        return np.random.default_rng(seed).integers(
            0, vocab, (rows, t), dtype=np.int32)
    return np.random.RandomState(seed).randint(0, vocab, (rows, t)).astype(
        np.int32)


def flat(tree):
    """``{"layers_0/attn/wq": leaf}``."""
    return {"/".join(str(k.key) for k in path): leaf for path, leaf in
            jax.tree_util.tree_leaves_with_path(tree)}


def assert_every_gradient_matches(grads, want_grads, rtol, takes_none):
    """The same leaves on both sides, each within ``rtol`` of the
    reference's OWN norm; the leaves ``takes_none(name)`` names are zero
    on both sides, every other is not zero in the reference."""
    got, want_grads = flat(grads), flat(want_grads)
    assert set(got) == set(want_grads)
    for name, g in want_grads.items():
        assert float(jnp.linalg.norm(got[name] - g)) <= rtol * float(
            jnp.linalg.norm(g)), name
        if takes_none(name):
            assert not np.asarray(got[name]).any(), name
            assert not np.asarray(g).any(), name
        else:
            assert np.asarray(g).any(), name
    return got


TOKENS, TOP_K, HELD, TOTAL, D = 64, 2, (4, 2), 16, 32
CHUNK = 32
RUNGS = (32, 64, 96, 128)
PICKS = {0: (4, 5), 1: (4, 9), 2: (8, 9)}
#: rows routed here -> the chunks that hold them: no pick held (the first
#: chunk runs all the same), inside the first chunk, exactly a chunk, one
#: row more (expert 4's 17 rows, then expert 5's 16 CUT across the
#: boundary), two chunks exactly (the boundary is the groups'), one row
#: more, and every pick held: all ``N * k / C`` chunks
LOADS = {0: 1, 16: 1, 32: 1, 33: 2, 64: 2, 65: 3, 128: 4}
#: the loads at which a chunk's boundary cuts an expert's group: that
#: expert's gradient is then the sum of two products' and not one's
CUT = (33, 65, 128)


def layer(scoring: str, seed: int = 0) -> dict:
    params = moe.init_routed_moe_params(
        jax.random.key(seed), D, 12, TOTAL, experts_held=HELD[1],
        d_shared=24 if scoring == "sigmoid" else 0,
        selection_bias=scoring == "sigmoid")
    params = jax.tree_util.tree_map(lambda a: a * 8.0, params)     # lively
    router = np.zeros((D, TOTAL), np.float32)
    for kind, picks in PICKS.items():
        router[kind] = -6.0
        router[kind, list(picks)] = 6.0
    params["router"] = jnp.asarray(router)
    if "router_bias" in params:
        params["router_bias"] = jnp.zeros((TOTAL,), jnp.float32)
    return params


def tokens_routing(load: int, seed: int = 1) -> jax.Array:
    """``[TOKENS, D]`` of which exactly ``load`` picks are routed to the
    held experts."""
    two, one = divmod(load, 2)
    kinds = np.full((TOKENS,), 2)
    kinds[:two] = 0
    kinds[two:two + one] = 1
    np.random.default_rng(seed).shuffle(kinds)
    x = np.array(jax.random.normal(jax.random.key(seed), (TOKENS, D)))
    x[:, :3] = np.eye(3, dtype=np.float32)[kinds]
    return jnp.asarray(x)


def written_out(params, x, scoring, top_k=TOP_K):
    """The layer written out plainly: every held expert over every token,
    weighted by the router's weights; no sort, no chunk, no kernel."""
    first, count = HELD
    scores = jnp.dot(x, params["router"],
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(scores) if scoring == "sigmoid" \
        else jax.nn.softmax(scores, axis=-1)
    ranked = scores + jax.lax.stop_gradient(params.get("router_bias", 0.0))
    _, chosen = jax.lax.top_k(ranked, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(
            2.448 * picked / picked.sum(-1, keepdims=True))
    each = jax.vmap(lambda w: moe.swiglu(w, x))(params["experts"])
    y = jnp.einsum("ne,end->nd", gates[:, first:first + count], each)
    if "shared" in params:
        y = y + moe.swiglu(params["shared"], x)
    return y, jnp.sum(chosen.reshape(-1)[:, None]
                      == first + jnp.arange(count), axis=0)


def value_and_gradients(params, x, scoring, chunk=None, top_k=TOP_K,
                        layer_fn=None):
    """``sum(y ** 2)``, the tokens an expert was sent and the gradient of
    every leaf and of ``x``; ``chunk``: the places of a chunk in the
    shapes' place; ``layer_fn``: another layer in ``routed_moe_ffn``'s."""
    def loss(params, x):
        y, counts = moe.routed_moe_ffn(
            params, x, top_k=top_k, experts_held=HELD, routed_scale=2.448,
            scoring=scoring) if layer_fn is None else layer_fn(
            params, x, scoring, top_k)
        return jnp.sum(y ** 2), counts

    real = moe.chunk_rows
    if chunk is not None:
        moe.chunk_rows = lambda *shapes: chunk
    try:
        (value, counts), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(params, x)
    finally:
        moe.chunk_rows = real
    return value, counts, grads


def rel(got, want):
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-12))


def assert_chunks_equal_one_wide_chunk(scoring: str, load: int):
    """The call with ``load`` picks routed here takes ``LOADS[load]``
    chunks, and its value and every gradient are those of ONE chunk as
    wide as all the picks (the path before the chunks) TO THE BIT: a
    token's terms are added one after the other in the order of their
    experts, across chunks too.  But an expert whose group a boundary cuts:
    its gradient is the sum of two products' where the wide chunk forms
    one, a few 1e-7 apart.  Operation by operation (``disable_jit``):
    compiled, XLA's CPU backend fuses a loop's body and the same
    operations outside it in another order of float32 sums.  And both are
    the layer written out, within the order of its sums."""
    params, x = layer(scoring), tokens_routing(load)
    with jax.disable_jit():
        value, counts, grads = value_and_gradients(params, x, scoring)
        wide = value_and_gradients(params, x, scoring, chunk=TOKENS * TOP_K)
        plain = value_and_gradients(params, x, scoring, layer_fn=written_out)
    assert moe.chunk_rows(TOKENS * TOP_K, HELD[1], TOTAL) == CHUNK
    assert int(counts.sum()) == load
    np.testing.assert_array_equal(counts, plain[1])
    rungs, calls = moe.budgets_taken(counts, TOKENS * TOP_K, TOTAL)
    assert rungs == RUNGS
    assert calls.tolist() == [int(i + 1 == LOADS[load]) for i in range(4)]
    np.testing.assert_array_equal(value, wide[0])
    assert abs(float(value) - float(plain[0])) <= 2e-5 * float(plain[0])
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    assert len(leaves) == len(jax.tree_util.tree_leaves(params)) + 1
    for (path, got), want, written in zip(
            leaves, jax.tree_util.tree_leaves(wide[2]),
            jax.tree_util.tree_leaves(plain[2])):
        name = jax.tree_util.keystr(path)
        if load in CUT and "experts" in name:
            assert rel(got, want) < 1e-6, name
        else:
            np.testing.assert_array_equal(got, want, name)
        assert rel(got, written) < 2e-5 or not np.any(written), name
        if load and "router_bias" not in name:
            assert np.any(np.asarray(got)), name


@contextlib.contextmanager
def grouped_products_by_the_kernels():
    """The routed layer as a TPU traces it: its grouped products are the
    kernels of ``ops/grouped_matmul.py`` (interpreted here; off a TPU the
    layer takes ``jax.lax.ragged_dot``)."""
    real = moe._grouped_product
    moe._grouped_product = grouped_matmul.grouped_matmul
    try:
        yield
    finally:
        moe._grouped_product = real


#: the routed layer's own kernels, by the name a trace prints
LAYER_KERNELS = ("rows_to_tokens", "grouped_rows", "grouped_rows_t",
                 "grouped_weights")


def primitives(jaxpr) -> collections.Counter:
    """How often each primitive stands in a jaxpr, the routed layer's
    kernels (the sorted rows' return to token order, the grouped products)
    under their own names: what is left under ``pallas_call`` are a
    model's attention kernels."""
    return collections.Counter(
        eqn.params["name"] if eqn.primitive.name == "pallas_call"
        and eqn.params["name"] in LAYER_KERNELS else eqn.primitive.name
        for eqn in equations(jaxpr))


def chunk_loops(jaxpr):
    """The forward's and the backward's loop over the further chunks; no
    conditional beside them."""
    found = primitives(jaxpr)
    assert found["cond"] == 0 and found["while"] == 2, found
    return [eqn for eqn in equations(jaxpr) if eqn.primitive.name == "while"]


def assert_gradient_loops_once_a_direction_and_fills_no_rows(scoring: str):
    """The gradient's jaxpr: NO ``cond`` or ``switch``, one ``while`` for
    the forward's further chunks and one for the backward's, whose carries
    are token-shaped, weight-shaped or one number a pick alone; nothing in
    it, inside the loops or outside, is an ``[N * k, d]`` array, and each
    direction returns to token order by one kernel for the first chunk and
    one in the loop."""
    params, x = layer(scoring), tokens_routing(16)
    jaxpr = jax.make_jaxpr(lambda p, x: value_and_gradients(
        p, x, scoring)[2])(params, x).jaxpr
    for loop in chunk_loops(jaxpr):
        for var in loop.outvars:
            shape = var.aval.shape
            assert len(shape) < 2 or shape[0] in (TOKENS, HELD[1]), shape
        assert primitives(loop.params["body_jaxpr"].jaxpr)[
            "rows_to_tokens"] == 1
    for eqn in equations(jaxpr):
        for var in eqn.outvars:
            assert getattr(var.aval, "shape", None) != (TOKENS * TOP_K, D), \
                eqn
    assert primitives(jaxpr)["rows_to_tokens"] == 4


def assert_nothing_is_as_wide_as_the_picks(scoring: str, top_k: int):
    """96 tokens pick ``top_k`` of 16 experts (a chunk is twice the 12 an
    even router sends to the two held, in whole 8-row tiles): NO equation
    of the gradient, in the first chunk's path or in the loops', yields
    ``N * top_k * d`` elements or more: no ``[N, k, d]`` array (at k = 6 a
    relayout on the chip), no select or fill of every pick's row; the
    grouped products, as a TPU runs them (the kernels of
    ``ops/grouped_matmul.py``, found by name), work on a chunk's rows."""
    tokens = 96
    params = jax.eval_shape(lambda: layer(scoring))
    x = jax.ShapeDtypeStruct((tokens, D), jnp.float32)
    chunk = moe.chunk_rows(tokens * top_k, HELD[1], TOTAL)
    assert chunk == 24 * top_k and tokens * top_k // chunk == 4
    with grouped_products_by_the_kernels():
        jaxpr = jax.make_jaxpr(lambda p, x: value_and_gradients(
            p, x, scoring, top_k=top_k)[2])(params, x).jaxpr
    chunk_loops(jaxpr)
    for eqn in equations(jaxpr):
        for var in eqn.outvars:
            assert var.aval.size < tokens * top_k * D, eqn
        if eqn.primitive.name == "pallas_call" \
                and eqn.params["name"].startswith("grouped_"):
            # after the visits' four arrays of integers: the rows
            assert eqn.invars[4].aval.shape[0] == chunk, eqn
    # the first chunk's and the loop's, each: the forward's three, and
    # backward the forward's three again, the rows' three cotangents and
    # the weights' three; XLA's own grouped product is gone
    found = primitives(jaxpr)
    assert (found["grouped_rows"], found["grouped_rows_t"],
            found["grouped_weights"], found["ragged_dot_general"]) == (
        2 * 3 + 2 * 3, 2 * 3, 2 * 3, 0), found


def poisoned(grouped, traced: list):
    """``parallel/moe.py: _grouped_swiglu`` as a chip runs it: rows past
    the last group come back UNWRITTEN, from the products and from their
    transposes (a CPU's ``ragged_dot`` writes zeros there): NaN here.
    ``traced`` takes the rows of every call traced."""
    def unwritten(rows, sizes):
        past = jnp.arange(rows.shape[0]) >= sizes.sum()
        return jnp.where(past[:, None], jnp.nan, rows)

    def on_a_chip(experts, rows, sizes, activation=jax.nn.silu):
        traced.append(rows.shape[0])

        @jax.custom_vjp
        def products(experts, rows, sizes):
            return unwritten(grouped(experts, rows, sizes, activation),
                             sizes)

        def forward(experts, rows, sizes):
            out, transpose = jax.vjp(lambda e, r: grouped(
                e, r, sizes, activation), experts, rows)
            return unwritten(out, sizes), (transpose, sizes)

        def backward(kept, g):
            transpose, sizes = kept
            d_experts, d_rows = transpose(g)
            return d_experts, unwritten(d_rows, sizes), None

        products.defvjp(forward, backward)
        return products(experts, rows, sizes)

    return on_a_chip


def assert_unwritten_rows_are_never_read(scoring: str, load: int):
    """Value and every gradient with the rows past the last group NaN in
    every chunk: finite, and what they are with zeros there.  ``load`` 16
    fills half of the first chunk, 32 fills it EXACTLY, 64 two chunks, and
    picks of absent experts are present, whose places lie past the live
    rows."""
    params, x = layer(scoring), tokens_routing(load)
    run = jax.jit(lambda p, x: value_and_gradients(p, x, scoring))
    want = run(params, x)
    real, traced = moe._grouped_swiglu, []
    moe._grouped_swiglu = poisoned(real, traced)
    try:
        got = jax.jit(lambda p, x: value_and_gradients(p, x, scoring))(
            params, x)
    finally:
        moe._grouped_swiglu = real
    # the first chunk and the loop's body, forward and backward
    assert int(got[1].sum()) == load and traced == [CHUNK] * 4
    for (path, leaf), other in zip(
            jax.tree_util.tree_leaves_with_path(got),
            jax.tree_util.tree_leaves(want)):
        assert np.all(np.isfinite(leaf)), jax.tree_util.keystr(path)
        np.testing.assert_array_equal(leaf, other,
                                      jax.tree_util.keystr(path))


def tiles_gauges():
    """``autodist_moe_grouped_row_tiles_per_step``: (visited, live)."""
    from autodist_tpu.telemetry.registry import DEFAULT_REGISTRY

    found = {m.labels["kind"]: m.value for m in DEFAULT_REGISTRY.metrics()
             if m.name == "autodist_moe_grouped_row_tiles_per_step"}
    return found.get("visited"), found.get("live")


def tiles_written_out(counts, chunk: int):
    """(visited, live) of calls whose ``tokens_per_expert`` are the rows of
    ``counts``, plainly: the chunks a call takes, the tiles of each that
    hold a routed row (a chunk is whole tiles), and those once more for
    every further group that has rows in them."""
    tile, _ = grouped_matmul._row_tile(chunk)
    visited = live = 0
    for sizes in np.asarray(counts).reshape(-1, np.shape(counts)[-1]):
        group = np.repeat(np.arange(len(sizes)), sizes)
        for start in range(0, len(group), tile):
            live += 1
            visited += len(set(group[start:start + tile]))
    return visited, live


def budget_gauges():
    """(rows of the chunks the last fetched step's calls took, calls by
    the rows their chunks covered so far)."""
    from autodist_tpu.telemetry.registry import DEFAULT_REGISTRY

    rows, calls = None, {}
    for m in DEFAULT_REGISTRY.metrics():
        if m.name == "autodist_moe_rows_per_step" \
                and m.labels["kind"] == "computed":
            rows = m.value
        elif m.name == "autodist_moe_row_budget_calls_total":
            calls[int(m.labels["rung"])] = m.value
    return rows, calls
