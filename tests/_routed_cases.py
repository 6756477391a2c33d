"""A routed expert layer whose load is chosen to the row, for the tests of
``parallel/moe.py``'s ladder of row budgets (``test_mla_moe_lm.py`` for the
sigmoid router, ``test_gqa_dsa_moe_lm.py`` for the softmax one).

64 tokens pick 2 of 16 experts, experts 4 and 5 are held: 128 picks a call,
an even router sends 16 here, so the rungs are 32, 64 and 128 rows.  A
token's first three columns say its KIND and the router reads nothing else:
kind 0 picks experts 4 and 5 (two picks here), kind 1 picks 4 and 9 (one),
kind 2 picks 8 and 9 (none).  The other columns are noise, so that the
experts' outputs differ from token to token."""
import collections

import jax
import jax.numpy as jnp
import numpy as np

from autodist_tpu.models.mla_moe_lm import equations
from autodist_tpu.parallel import moe

TOKENS, TOP_K, HELD, TOTAL, D = 64, 2, (4, 2), 16, 32
RUNGS = (32, 64, 128)
PICKS = {0: (4, 5), 1: (4, 9), 2: (8, 9)}
#: rows routed here -> the rung that holds them: the boundaries (a load
#: of exactly a rung takes it, one more takes the next), none and all
LOADS = {0: 0, 32: 0, 33: 1, 64: 1, 65: 2, 128: 2}


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


def value_and_gradients(params, x, scoring, rungs=None, top_k=TOP_K):
    """``sum(y ** 2)``, the tokens an expert was sent and the gradient of
    every leaf and of ``x``; ``rungs``: the ladder in the shapes' place."""
    def loss(params, x):
        y, counts = moe.routed_moe_ffn(
            params, x, top_k=top_k, experts_held=HELD, routed_scale=2.448,
            scoring=scoring)
        return jnp.sum(y ** 2), counts

    real = moe.row_budgets
    if rungs is not None:
        moe.row_budgets = lambda *shapes: rungs
    try:
        (value, counts), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(params, x)
    finally:
        moe.row_budgets = real
    return value, counts, grads


def assert_rung_equals_the_top_rung(scoring: str, load: int):
    """The call with ``load`` picks routed here takes ``LOADS[load]``, and
    its value and every gradient are the top rung's TO THE BIT.  Operation
    by operation (``disable_jit``): compiled, XLA's CPU backend fuses a
    branch's body and the same operations outside a ``switch`` in another
    order of float32 sums (a few 1e-7)."""
    params, x = layer(scoring), tokens_routing(load)
    with jax.disable_jit():
        value, counts, grads = value_and_gradients(params, x, scoring)
        top = value_and_gradients(params, x, scoring, rungs=RUNGS[-1:])
    assert moe.row_budgets(TOKENS * TOP_K, HELD[1], TOTAL) == RUNGS
    assert int(counts.sum()) == load
    rungs, calls = moe.budgets_taken(counts, TOKENS * TOP_K, TOTAL)
    assert rungs == RUNGS
    assert calls.tolist() == [int(i == LOADS[load]) for i in range(3)]
    np.testing.assert_array_equal(value, top[0])
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    assert len(leaves) == len(jax.tree_util.tree_leaves(params)) + 1
    for (path, got), want in zip(leaves, jax.tree_util.tree_leaves(top[2])):
        name = jax.tree_util.keystr(path)
        np.testing.assert_array_equal(got, want, name)
        if load and "router_bias" not in name:
            assert np.any(np.asarray(got)), name


def primitives(jaxpr) -> collections.Counter:
    """How often each primitive stands in a jaxpr, the kernels that bring
    sorted rows back to token order under their own name: what is left
    under ``pallas_call`` are a model's attention kernels."""
    return collections.Counter(
        "rows_to_tokens" if eqn.primitive.name == "pallas_call"
        and eqn.params["name"] == "rows_to_tokens" else eqn.primitive.name
        for eqn in equations(jaxpr))


def rung_switches(jaxpr):
    """The forward's and the backward's ``switch`` over the rungs."""
    found = [eqn for eqn in equations(jaxpr) if eqn.primitive.name == "cond"]
    assert [len(eqn.params["branches"]) for eqn in found] == [3, 3]
    return found


def assert_gradient_switches_once_and_fills_no_rows(scoring: str):
    """The gradient's jaxpr: one ``switch`` of three branches for the
    forward and one for the backward, whose results are token-shaped or
    weight-shaped alone; and but for the top rung's branches, which work on
    all ``N * k`` rows, nothing broadcasts into an ``[N * k, d]`` array
    (differentiated as written, ``switch`` would zero-fill the top rung's
    residuals in every other branch)."""
    def fills(jaxpr):
        return sum(eqn.primitive.name == "broadcast_in_dim"
                   and eqn.outvars[0].aval.shape == (TOKENS * TOP_K, D)
                   for eqn in equations(jaxpr))

    params, x = layer(scoring), tokens_routing(16)
    jaxpr = jax.make_jaxpr(lambda p, x: value_and_gradients(
        p, x, scoring)[2])(params, x).jaxpr
    in_top = 0
    for eqn in rung_switches(jaxpr):
        assert all(var.aval.shape[0] in (TOKENS, HELD[1])
                   for var in eqn.outvars), eqn.outvars
        in_top += fills(eqn.params["branches"][-1].jaxpr)
    assert fills(jaxpr) == in_top > 0


def assert_nothing_below_the_top_rung_is_as_wide_as_the_picks(
        scoring: str, top_k: int):
    """96 tokens pick ``top_k`` of 16 experts (rungs of 2, 4 and 8 times
    the 12 an even router sends to the two held): in the branches of the
    gradient's two ``switch`` es below the top rung NO equation yields
    ``N * top_k * d`` elements or more: no ``[N, k, d]`` array (at k = 6 a
    relayout on the chip), no select or fill of every pick's row; sorted
    rows come back by ``rows_to_tokens``, once a branch."""
    tokens = 96
    params = jax.eval_shape(lambda: layer(scoring))
    x = jax.ShapeDtypeStruct((tokens, D), jnp.float32)
    assert len(moe.row_budgets(tokens * top_k, HELD[1], TOTAL)) == 3
    jaxpr = jax.make_jaxpr(lambda p, x: value_and_gradients(
        p, x, scoring, top_k=top_k)[2])(params, x).jaxpr
    for eqn in rung_switches(jaxpr):
        for branch in eqn.params["branches"][:-1]:
            for inner in equations(branch.jaxpr):
                for var in inner.outvars:
                    assert var.aval.size < tokens * top_k * D, inner
            assert primitives(branch.jaxpr)["rows_to_tokens"] == 1


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
    """Value and every gradient with the rows past the last group NaN:
    finite, and what they are with zeros there.  ``load`` 16 fills half
    of the first rung; 32 and 64 fill a rung EXACTLY with picks of absent
    experts present, whose clipped places fall on a live row."""
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
    # forward and backward of every rung
    assert int(got[1].sum()) == load and sorted(traced) == sorted(2 * RUNGS)
    for (path, leaf), other in zip(
            jax.tree_util.tree_leaves_with_path(got),
            jax.tree_util.tree_leaves(want)):
        assert np.all(np.isfinite(leaf)), jax.tree_util.keystr(path)
        np.testing.assert_array_equal(leaf, other,
                                      jax.tree_util.keystr(path))


def budget_gauges():
    """(rows of the budgets the last fetched step took, calls by rung so
    far)."""
    from autodist_tpu.telemetry.registry import DEFAULT_REGISTRY

    rows, calls = None, {}
    for m in DEFAULT_REGISTRY.metrics():
        if m.name == "autodist_moe_rows_per_step" \
                and m.labels["kind"] == "computed":
            rows = m.value
        elif m.name == "autodist_moe_row_budget_calls_total":
            calls[int(m.labels["rung"])] = m.value
    return rows, calls
