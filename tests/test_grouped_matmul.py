"""``ops/grouped_matmul.py`` interpreted on a CPU: each of the three kinds
of grouped product against ``jax.lax.ragged_dot`` and its transposes at
the kernels' rounding (operands to bfloat16, products summed in float32),
the visits the kernels are scheduled on, and the routed expert layer
through them (``parallel/moe.py`` takes ``ragged_dot`` off a TPU: here it
is handed the kernels)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _routed_cases as routed_cases
from autodist_tpu.ops import grouped_matmul as gm
from autodist_tpu.parallel import moe

K, N, E = 24, 40, 4
#: rows, the row tile asked for, the groups' sizes
GROUPS = {
    "a_boundary_inside_a_tile": (64, 16, (10, 9, 23, 7)),
    "groups_of_no_rows": (64, 16, (0, 20, 0, 30)),
    "one_group_over_every_tile": (64, 16, (64, 0, 0, 0)),
    "boundaries_on_the_tiles": (64, 16, (16, 16, 16, 16)),
    "four_groups_in_one_tile": (64, 16, (1, 2, 1, 3)),
    "all_rows_past_the_last_group": (64, 16, (0, 0, 0, 0)),
    "the_last_group_alone": (64, 16, (0, 0, 0, 50)),
    "rows_no_multiple_of_the_tile": (48, 512, (5, 20, 0, 14)),   # tile 16
    "rows_no_multiple_of_eight": (36, 512, (7, 0, 20, 9)),       # one tile
    "one_tile": (64, 512, (10, 0, 23, 7)),
}


def rounded(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def operands(rows, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(keys[0], (rows, K)),
            jax.random.normal(keys[1], (E, K, N)),
            jax.random.normal(keys[2], (rows, N)))


def by_xla(x, w, sizes):
    """``(the product, its transposes)`` by XLA at the kernels' rounding."""
    return jax.vjp(lambda x, w: jax.lax.ragged_dot(
        x, w, sizes, precision=jax.lax.Precision.HIGHEST),
        rounded(x), rounded(w))


def close(got, want, live=None):
    got, want = np.asarray(got)[:live], np.asarray(want)[:live]
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.fixture
def tile(request, monkeypatch):
    rows, tile, sizes = GROUPS[request.param]
    monkeypatch.setattr(gm, "ROW_TILE", tile)
    monkeypatch.setattr(gm, "_PART", 8)     # a tile of 16 rows: two parts
    return rows, jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("tile", sorted(GROUPS), indirect=True)
def test_rows_under_their_groups_weights(tile):
    rows, sizes = tile
    x, w, _ = operands(rows)
    close(gm.grouped_rows(x, w, sizes), by_xla(x, w, sizes)[0],
          int(sizes.sum()))


@pytest.mark.parametrize("tile", sorted(GROUPS), indirect=True)
def test_rows_under_their_groups_weights_transposed(tile):
    """The rows' cotangent: the weights are read as they lie and turned in
    the kernel."""
    rows, sizes = tile
    x, w, g = operands(rows)
    want = by_xla(jnp.zeros_like(x), w, sizes)[1](rounded(g))[0]
    close(gm.grouped_rows(g, w, sizes, transposed=True), want,
          int(sizes.sum()))


@pytest.mark.parametrize("tile", sorted(GROUPS), indirect=True)
def test_each_groups_rows_contracted(tile):
    """The weights' gradient: zeros for a group without rows, and what
    lies past the last group (NaN here, as a kernel leaves it) is not
    read as numbers."""
    rows, sizes = tile
    x, w, g = operands(rows)
    want = by_xla(x, jnp.zeros_like(w), sizes)[1](rounded(jnp.where(
        jnp.arange(rows)[:, None] < sizes.sum(), g, 0)))[1]
    past = jnp.arange(rows)[:, None] >= sizes.sum()
    got = gm.grouped_weights(jnp.where(past, jnp.nan, x),
                             jnp.where(past, jnp.nan, g), sizes)
    close(got, want)
    for e, size in enumerate(sizes.tolist()):
        assert size or not np.any(got[e])


@pytest.mark.parametrize("tile", sorted(GROUPS), indirect=True)
def test_the_product_differentiates_by_the_other_two_kernels(tile):
    rows, sizes = tile
    x, w, g = operands(rows)
    live = int(sizes.sum())
    g = jnp.where(jnp.arange(rows)[:, None] < live, g, 0)
    want_out, want_t = by_xla(x, w, sizes)
    out, transpose = jax.vjp(lambda x, w: gm.grouped_matmul(x, w, sizes),
                             x, w)
    close(out, want_out, live)
    d_x, d_w = transpose(g)
    want_x, want_w = want_t(rounded(g))
    close(d_x, want_x, live)
    close(d_w, want_w)


def visits_written_out(sizes, rows, tile, empty_groups):
    """(group, row tile) of every visit, plainly."""
    found, start = [], 0
    for group, size in enumerate(sizes):
        if size:
            found += [(group, t) for t in range(
                start // tile, (start + size - 1) // tile + 1)]
        elif empty_groups:
            found.append((group, min(start // tile, rows // tile - 1)))
        start += size
    return found


@pytest.mark.parametrize("empty_groups", [False, True])
@pytest.mark.parametrize("tile", sorted(GROUPS), indirect=True)
def test_visits_are_the_tiles_that_hold_a_groups_rows(tile, empty_groups):
    """A group without rows is visited once where its gradient has to be
    written (on any tile: nothing of it is read) and else not at all."""
    rows, sizes = tile
    tile, _ = gm._row_tile(rows)
    want = visits_written_out(sizes.tolist(), rows, tile, empty_groups)
    offsets, groups, tiles, visits = gm._visits(sizes, rows, tile,
                                                empty_groups)
    assert offsets.tolist() == [0] + np.cumsum(sizes).tolist()
    assert int(visits[0]) == len(want)
    assert groups.shape == tiles.shape == (rows // tile + E - 1,)
    got = list(zip(groups.tolist(), tiles.tolist()))
    for (group, at), (want_group, want_at) in zip(got, want):
        assert group == want_group
        assert at == want_at or not sizes[group]
    # the entries past the last visit repeat it: nothing is fetched
    assert set(got[len(want):]) <= {got[max(len(want) - 1, 0)]}
    assert all(0 <= t < rows // tile for t in tiles.tolist())
    if not empty_groups:
        visited, live = gm.row_tiles(sizes[None], rows)
        assert (visited.tolist(), live.tolist()) == (
            [len(want)], [-(-int(sizes.sum()) // tile)])


@pytest.mark.parametrize("activation", [jax.nn.silu, jax.nn.relu],
                         ids=["silu", "relu"])
def test_grouped_swiglu_value_and_gradient(activation, monkeypatch):
    """``parallel/moe.py: _grouped_swiglu`` through the kernels, value and
    every gradient, against the same through ``ragged_dot`` in float32:
    one bfloat16 pass apart (a few 1e-3 of the norm), whatever the
    gate."""
    monkeypatch.setattr(gm, "ROW_TILE", 16)
    monkeypatch.setattr(gm, "_PART", 8)
    rows, sizes = 64, jnp.asarray((10, 0, 23, 7), jnp.int32)
    live = int(sizes.sum())
    keys = jax.random.split(jax.random.key(3), 5)
    experts = {"w_gate": jax.random.normal(keys[0], (E, K, N)) * K ** -0.5,
               "w_up": jax.random.normal(keys[1], (E, K, N)) * K ** -0.5,
               "w_down": jax.random.normal(keys[2], (E, N, K)) * N ** -0.5}
    x = jax.random.normal(keys[3], (rows, K))
    g = jax.random.normal(keys[4], (rows, K))

    def loss(experts, x):
        out = moe._grouped_swiglu(experts, x, sizes, activation)
        return jnp.sum(jnp.where(jnp.arange(rows)[:, None] < live,
                                 out * g, 0))

    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(loss, argnums=(0, 1))(experts, x)
    with routed_cases.grouped_products_by_the_kernels():
        got = jax.value_and_grad(loss, argnums=(0, 1))(experts, x)
    assert abs(float(got[0]) - float(want[0])) < 2e-2 * float(
        jnp.linalg.norm(g[:live]))
    for (path, leaf), other in zip(
            jax.tree_util.tree_leaves_with_path(got[1]),
            jax.tree_util.tree_leaves(want[1])):
        if leaf.shape == x.shape:
            leaf, other = leaf[:live], other[:live]
        assert np.all(np.isfinite(leaf)), jax.tree_util.keystr(path)
        assert routed_cases.rel(leaf, other) < 1e-2, \
            jax.tree_util.keystr(path)


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
@pytest.mark.parametrize("load", [16, 32, 33, 64])
def test_the_layer_never_reads_what_a_kernel_leaves_unwritten(
        scoring, load, monkeypatch):
    """The routed layer of ``_routed_cases`` with the kernels for its
    grouped products (interpreted, what a kernel does not write is NaN;
    a chunk's last tile is part written) AND with
    ``_routed_cases.poisoned`` around them: value and every gradient
    finite, and those of the layer through ``ragged_dot`` but for the
    rounding (tiles of 16 rows in parts of 8).  ``load`` 16 fills half of
    the first chunk, 32 fills it, 33 cuts a group across two chunks, 64
    fills two."""
    monkeypatch.setattr(gm, "ROW_TILE", 16)
    monkeypatch.setattr(gm, "_PART", 8)
    params, x = routed_cases.layer(scoring), routed_cases.tokens_routing(load)

    def run():          # traced anew: the products are another platform's
        return jax.jit(lambda p, x: routed_cases.value_and_gradients(
            p, x, scoring))(params, x)

    with jax.default_matmul_precision("highest"):
        want = run()
    traced = []
    monkeypatch.setattr(moe, "_grouped_swiglu", routed_cases.poisoned(
        moe._grouped_swiglu, traced))
    with routed_cases.grouped_products_by_the_kernels():
        got = run()
    assert int(got[1].sum()) == load and traced == [routed_cases.CHUNK] * 4
    for (path, leaf), other in zip(
            jax.tree_util.tree_leaves_with_path(got),
            jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        assert np.all(np.isfinite(leaf)), name
        if np.any(np.asarray(other)):
            assert routed_cases.rel(jnp.asarray(leaf, jnp.float32),
                                    jnp.asarray(other, jnp.float32)) < 2e-2, \
                name
