"""A routed expert layer whose load is chosen to the row, for the tests of
``parallel/moe.py``'s ladder of row budgets (``test_mla_moe_lm.py`` for the
sigmoid router, ``test_gqa_dsa_moe_lm.py`` for the softmax one).

64 tokens pick 2 of 16 experts, experts 4 and 5 are held: 128 picks a call,
an even router sends 16 here, so the rungs are 32, 64 and 128 rows.  A
token's first three columns say its KIND and the router reads nothing else:
kind 0 picks experts 4 and 5 (two picks here), kind 1 picks 4 and 9 (one),
kind 2 picks 8 and 9 (none).  The other columns are noise, so that the
experts' outputs differ from token to token."""
import jax
import jax.numpy as jnp
import numpy as np

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


def value_and_gradients(params, x, scoring, rungs=None):
    """``sum(y ** 2)``, the tokens an expert was sent and the gradient of
    every leaf and of ``x``; ``rungs``: the ladder in the shapes' place."""
    def loss(params, x):
        y, counts = moe.routed_moe_ffn(
            params, x, top_k=TOP_K, experts_held=HELD, routed_scale=2.448,
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


def assert_gradient_switches_once_and_fills_no_rows(scoring: str):
    """The gradient's jaxpr: one ``switch`` of three branches for the
    forward and one for the backward, whose results are token-shaped or
    weight-shaped alone; and but for the top rung's branches, which work on
    all ``N * k`` rows, nothing broadcasts into an ``[N * k, d]`` array
    (differentiated as written, ``switch`` would zero-fill the top rung's
    residuals in every other branch)."""
    from autodist_tpu.models.mla_moe_lm import equations

    def fills(jaxpr):
        return sum(eqn.primitive.name == "broadcast_in_dim"
                   and eqn.outvars[0].aval.shape == (TOKENS * TOP_K, D)
                   for eqn in equations(jaxpr))

    params, x = layer(scoring), tokens_routing(16)
    jaxpr = jax.make_jaxpr(lambda p, x: value_and_gradients(
        p, x, scoring)[2])(params, x).jaxpr
    found = [eqn for eqn in equations(jaxpr) if eqn.primitive.name == "cond"]
    assert [len(eqn.params["branches"]) for eqn in found] == [3, 3]
    in_top = 0
    for eqn in found:
        assert all(var.aval.shape[0] in (TOKENS, HELD[1])
                   for var in eqn.outvars), eqn.outvars
        in_top += fills(eqn.params["branches"][-1].jaxpr)
    assert fills(jaxpr) == in_top > 0


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
