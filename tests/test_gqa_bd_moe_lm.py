"""``models/gqa_bd_moe_lm.py`` (block diffusion on the routed decoder's
``objective`` seam) against the plain reference
``benchmark/reference/sdar.py`` (CPU, tiny widths, seeded weights).

Tolerances as in ``test_gqa_dsa_moe_lm.py``: a CPU multiplies float32
exactly, so program and reference differ by the order of their float32
sums alone; ``RTOL`` is 2e-5.  The noise is drawn in integers and float32
uniforms by one stated rule, which program and reference each write in
their own lines: their masks are equal to the bit.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.models.gqa_bd_moe_lm import KEPT_NAMES, gqa_bd_moe_lm, \
    noise_of
from autodist_tpu.models.routed_decoder import Objective, next_token
from autodist_tpu.ops import flash_attention
from autodist_tpu.ops.flash_attention import pairs_computed
from autodist_tpu.parallel.moe import init_routed_moe_params, routed_moe_ffn
from benchmark.reference import sdar as ref

import _routed_cases as routed_cases

RTOL = 2e-5
GAIN = 8.0
L, B = 96, 4
TINY = dict(vocab_size=61, num_layers=2, d_model=32, num_heads=4,
            num_kv_heads=2, head_dim=16, block_length=B, noise_seed=5,
            d_expert=12, num_experts=16, top_k=3, seq_len=L, block_k=32,
            moe_slice=96)
FLASH = functools.partial(flash_attention, interpret=True, block_q=32,
                          block_k=32)


def settings(first_held=0, top_k=3, train_router=True, **other):
    return ref.Settings(**{**dict(
        top_k=top_k, first_held=first_held, theta=1e6, eps=1e-6,
        block_length=B, noise_eps=1e-3, noise_seed=5, mask_id=60,
        train_router=train_router), **other})


seeded = functools.partial(routed_cases.seeded, gain=GAIN)
tokens, flat, rel = routed_cases.tokens, routed_cases.flat, routed_cases.rel


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("held,remat,chunk,train_router,attn,scale", [
    (None, "none", None, True, None, 1.0),
    ((4, 4), "full", 32, False, None, 4.0),
    ((0, 2), "full", 32, True, FLASH, 1.0),
])
def test_loss_and_every_gradient_match_the_reference(held, remat, chunk,
                                                     train_router, attn,
                                                     scale):
    spec = gqa_bd_moe_lm(**TINY, experts_held=held, remat=remat,
                         xent_chunk=chunk, train_router=train_router,
                         attn_fn=attn, embed_scale=scale)
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 3)
    batch = tokens(5, t=L)
    s = settings(held[0] if held else 0, train_router=train_router,
                 embed_scale=scale)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(spec.loss_fn))(
            params, {"tokens": batch})
        want, want_grads = ref.loss_and_grads(params, jnp.asarray(batch),
                                              row_block=2, s=s)
    assert abs(float(loss) - float(want)) < RTOL
    routed_cases.assert_every_gradient_matches(
        grads, want_grads, RTOL,
        lambda name: not train_router and "router" in name)


@pytest.mark.parametrize("other", ["causal_mask", "shifted"])
def test_another_mask_or_objective_is_another_loss(other):
    """The comparison sees the mechanism: the same weights under the plain
    causal mask over the 2 L rows, or asked
    for the NEXT token's weight give a loss that differs by 1e-3 or more."""
    batch = jnp.asarray(tokens(5, t=L))
    spec = gqa_bd_moe_lm(**TINY)
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 3)
    s = settings()
    with jax.default_matmul_precision("highest"):
        stated = float(jax.jit(spec.loss_fn)(params, {"tokens": batch}))
        if other == "shifted":
            moved = float(jax.jit(spec.loss_fn)(
                params, {"tokens": batch, "noised": noise_of(
                    batch, block_length=B, eps=1e-3, mask_id=60, seed=5)[0],
                    "loss_weight": jnp.roll(noise_of(
                        batch, block_length=B, eps=1e-3, mask_id=60,
                        seed=5)[1], 1, axis=1)}))
        else:
            s = settings(wrong="causal_mask")
            moved = float(ref.sum_loss_fn(params, batch, "float32",
                                          1.0 / batch.size, s=s))
    assert abs(stated - moved) > 1e-3


def test_three_session_steps_match_the_reference_adamw():
    """Through ``AutoDist.capture -> create_distributed_session -> run``
    with the kernel: three steps' losses and the parameters after them
    against the reference under AdamW written out; the gauges set at trace
    time, and the masked tokens of the last step as a step value."""
    from autodist_tpu import strategy as strategies
    from autodist_tpu.autodist import (AutoDist,
                                       _reset_default_autodist_for_testing)
    from autodist_tpu.mesh import build_mesh
    from autodist_tpu.telemetry.registry import DEFAULT_REGISTRY

    spec = gqa_bd_moe_lm(**TINY, experts_held=(4, 4), attn_fn=FLASH,
                         xent_chunk=32, train_router=False)
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 11)
    batches = [jnp.asarray(tokens(20 + i, t=L)) for i in range(3)]
    with jax.default_matmul_precision("highest"):
        want_losses, _, want_delta, _ = ref.train_steps(
            params, batches, row_block=2,
            s=settings(4, train_router=False))
        _reset_default_autodist_for_testing()
        ad = AutoDist(strategy_builder=strategies.AllReduce(),
                      mesh_axes={"data": 1})
        with ad.scope():
            ad.capture(params=params, optimizer=optax.adamw(1e-3),
                       loss_fn=spec.loss_fn, sparse_vars=spec.sparse_vars,
                       expert_vars=spec.expert_vars)
        sess = ad.create_distributed_session(
            mesh=build_mesh({"data": 1}, devices=jax.devices()[:1]))
        outs = [sess.run({"tokens": np.asarray(b)}) for b in batches]
        delta = ref.flatten(ref.leaf_diff_norms(
            sess.export_state()[0], params))
    _reset_default_autodist_for_testing()
    for out, want in zip(outs, want_losses):
        assert abs(float(out["loss"]) - want) < RTOL
    for name, want in want_delta.items():
        assert abs(delta[name] - want) <= 1e-4 * max(want, 1e-6), name
    gauges = {(m.name, m.labels.get("kind") or m.labels.get("name")): m.value
              for m in DEFAULT_REGISTRY.metrics()
              if m.name.startswith(("autodist_bd_", "autodist_remat_"))}
    # 2 sequences x 4 heads x 2 layers; L (L + B) / 2 in each half
    assert gauges[("autodist_bd_pairs_per_step", "attended")] \
        == 2 * 4 * 2 * L * (L + B)
    # by the model's key blocks of 32 (one q block a half): the clean
    # rows against 3, the noised rows against 3 clean and 3 noised
    assert gauges[("autodist_bd_pairs_per_step", "computed")] \
        == 2 * 4 * 2 * pairs_computed(2 * L, block_k=32,
                                      block_diffusion=(B, L)) \
        == 2 * 4 * 2 * 9 * 96 * 32
    assert [gauges[("autodist_bd_rows_per_step", kind)]
            for kind in ("data", "layers", "head")] == [2 * L, 4 * L, 2 * L]
    masked = int((noise_of(batches[2], block_length=B, eps=1e-3, mask_id=60,
                           seed=5)[1] > 0).sum())
    assert 0 < masked < 2 * L
    assert gauges[("autodist_bd_masked_tokens_per_step", None)] == masked
    # the kernel's output over the 2 L rows, a sequence and layer
    assert gauges[("autodist_remat_kept_bytes_per_step",
                   "flash_attention/o")] == 2 * 2 * 4 * 2 * L * 16 * 4
    assert set(KEPT_NAMES) <= {name for _, name in gauges}


# ---------------------------------------------------------------------------
# the two halves, the noise, the batch
# ---------------------------------------------------------------------------

def test_nothing_leaks_from_the_noised_half():
    """The clean half's outputs in the two-copy pass equal a block-causal
    pass over ``x`` alone (``apply_fn`` over ``L`` rows), whatever the
    noised copy holds; the noised half's do depend on it."""
    spec = gqa_bd_moe_lm(**TINY, attn_fn=FLASH)
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 3)
    x = jnp.asarray(tokens(5, t=L))
    apply = jax.jit(spec.apply_fn)
    alone = apply(params, x)
    assert alone.shape == (2, L, 61)
    copies = [noise_of(x, block_length=B, eps=1e-3, mask_id=60, seed=seed)[0]
              for seed in (5, 6)]
    both = [apply(params, jnp.concatenate([x, c], axis=1)) for c in copies]
    for out in both:
        np.testing.assert_allclose(out[:, :L], alone, rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(both[0][:, L:] - both[1][:, L:]).max()) > 1e-3
    # a clean row sees the later rows of its own block and none past it
    moved = apply(params, x.at[:, 6].set((x[:, 6] + 1) % 61))
    assert float(jnp.abs(moved[:, 4] - alone[:, 4]).max()) > 1e-4
    np.testing.assert_allclose(moved[:, :4], alone[:, :4], rtol=1e-5,
                               atol=1e-6)
    with pytest.raises(ValueError, match="a sequence has 96"):
        spec.apply_fn(params, x[:, :64])


def test_the_noise_is_the_sequences_own():
    """A sequence's levels and masks come from its own tokens and the
    seed: a batch of two cut into two batches of one draws the same, the
    reference draws the same to the bit, and a batch that brings its own
    ``noised`` and ``loss_weight`` is taken at its word."""
    x = jnp.asarray(tokens(9, t=L))
    rule = dict(block_length=B, eps=1e-3, mask_id=60, seed=5)
    noised, weight = noise_of(x, **rule)
    for row in range(2):
        one = noise_of(x[row:row + 1], **rule)
        np.testing.assert_array_equal(one[0][0], noised[row])
        np.testing.assert_array_equal(one[1][0], weight[row])
        want = ref.noised(x[row], settings())
        np.testing.assert_array_equal(want[0], noised[row])
        np.testing.assert_array_equal(want[1], weight[row])
    assert not np.array_equal(np.asarray(weight[0]), np.asarray(weight[1]))
    masked = np.asarray(weight) > 0
    assert (np.asarray(noised)[masked] == 60).all()
    assert (np.asarray(noised)[~masked] == np.asarray(x)[~masked]).all()
    # one level a block, between eps and 1
    blocks = np.asarray(weight).reshape(2, L // B, B)
    assert all(len(set(b[b > 0].tolist())) <= 1 for b in blocks.reshape(-1, B))
    assert blocks.max() <= 1e3 and blocks[blocks > 0].min() >= 1.0
    spec = gqa_bd_moe_lm(**TINY)
    params = seeded(jax.eval_shape(spec.init, jax.random.key(0)), 3)
    loss = jax.jit(spec.loss_fn)
    drawn = float(loss(params, {"tokens": x}))
    brought = float(loss(params, {"tokens": x, "noised": noised,
                                  "loss_weight": weight}))
    assert drawn == brought
    # the step's mean over two sequences is the mean of the steps over one
    halves = [float(loss(params, {"tokens": x[row:row + 1]}))
              for row in range(2)]
    assert abs(sum(halves) / 2 - drawn) < RTOL
    other = float(loss(params, {"tokens": x, "noised": noised,
                                "loss_weight": 2.0 * weight}))
    assert abs(other - 2.0 * drawn) < 1e-5


def test_the_seam_leaves_next_token_the_default():
    """``routed_decoder``'s objective: causal next-token unless a model
    says otherwise; an objective names the rows and the loss, nothing
    else."""
    causal = next_token(None)
    batch = {"tokens": jnp.arange(12).reshape(2, 6)}
    ids, carried = causal.rows(batch)
    assert ids is batch["tokens"] and carried is batch["tokens"]
    assert Objective._fields == ("rows", "loss")
    spec = gqa_bd_moe_lm(**TINY)
    assert spec.name == "gqa_bd_moe_lm" and spec.sparse_vars == ("embed",)
    assert spec.make_batch(np.random.RandomState(0), 2)["tokens"].shape \
        == (2, L)
    with pytest.raises(ValueError, match="blocks of 5"):
        gqa_bd_moe_lm(**dict(TINY, block_length=5))


# ---------------------------------------------------------------------------
# the share
# ---------------------------------------------------------------------------

def test_eight_shares_add_up_to_the_uncut_references_layer():
    """Eight chips hold 2 of 16 experts each: what each computes for its
    own experts adds up to the uncut reference's layer output, and each
    share is the reference's share."""
    params = jax.tree.map(lambda a: a * GAIN, init_routed_moe_params(
        jax.random.key(3), 32, 12, 16, selection_bias=False))
    x = jax.random.normal(jax.random.key(4), (2, 24, 32))
    flat_x = x.reshape(48, 32)
    whole = ref._experts(flat_x, params, settings(top_k=4), None)
    parts, counts = [], []
    for chip in range(8):
        share = dict(params, experts=jax.tree.map(
            lambda a: a[2 * chip:2 * chip + 2], params["experts"]))
        y, n = routed_moe_ffn(share, x, top_k=4, experts_held=(2 * chip, 2),
                              scoring="softmax")
        parts.append(y.reshape(48, 32))
        counts.append(n)
        want = ref._experts(flat_x, share, settings(2 * chip, top_k=4), None)
        assert rel(parts[-1], want) < RTOL
    assert rel(sum(parts), whole) < RTOL
    assert int(jnp.concatenate(counts).sum()) == 2 * 24 * 4   # every pick
