"""Flash-attention kernel vs the dense reference implementation.

The dense softmax (``models/transformer.py:dense_attention``) is the oracle:
forward outputs and gradients must agree to fp32 tolerance for causal and
full attention, including under a sharded mesh (shard_map manual path).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.mesh import build_mesh
from autodist_tpu.models.transformer import dense_attention
from autodist_tpu.ops import flash_attention, make_flash_attention


def _qkv(rng, b=2, t=32, h=2, d=16):
    shape = (b, t, h, d)
    q = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    k = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_dense(causal):
    q, k, v = _qkv(np.random.default_rng(0))
    out = flash_attention(q, k, v, causal, block_q=8, block_k=8)
    ref = dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_dense(causal):
    q, k, v = _qkv(np.random.default_rng(1), t=16, d=8)
    w = jnp.asarray(np.random.default_rng(2).standard_normal(
        (2, 16, 2, 8)), jnp.float32)

    def loss(attn):
        def f(q, k, v):
            return jnp.sum(attn(q, k, v, causal) * w)
        return f

    flash = lambda q, k, v, c: flash_attention(  # noqa: E731
        q, k, v, c, block_q=8, block_k=8)
    g_flash = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss(dense_attention), argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=1e-4, atol=1e-4, err_msg=f"d{name}")


def test_uneven_blocks_picks_divisor():
    # t=24 with requested block 128 → kernel must fall back to a divisor.
    q, k, v = _qkv(np.random.default_rng(3), t=24)
    out = flash_attention(q, k, v, True)
    ref = dense_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_pad_len_policy():
    from autodist_tpu.ops.flash_attention import _pad_len
    assert _pad_len(23, True) == 23          # interpret: no constraint
    assert _pad_len(23, False) == 24         # small: next multiple of 8
    assert _pad_len(128, False) == 128
    assert _pad_len(130, False) == 256       # large: next multiple of 128
    assert _pad_len(1, False) == 8


@pytest.mark.parametrize("causal", [False, True])
def test_padded_kernel_path_matches_dense(causal):
    """Drive the kv_len<T masked branches of all three kernels (the
    compiled-TPU padding path) in interpret mode: manually pad the inputs
    and pass the true kv_len through the private op, forward and backward.
    On real TPU `flash_attention` takes this path automatically for
    non-tileable lengths."""
    import importlib
    fa = importlib.import_module("autodist_tpu.ops.flash_attention")
    t = 23
    q, k, v = _qkv(np.random.default_rng(4), t=t, d=8)
    ref = dense_attention(q, k, v, causal)
    pad = [(0, 0), (0, 0), (0, 24 - t), (0, 0)]
    qt, kt, vt = (jnp.pad(x.transpose(0, 2, 1, 3), pad) for x in (q, k, v))

    o, _ = fa._flash(qt, kt, vt, causal, 8, 8, True, t)
    np.testing.assert_allclose(
        np.asarray(o[:, :, :t, :].transpose(0, 2, 1, 3)), np.asarray(ref),
        rtol=2e-5, atol=2e-5)

    w = jnp.asarray(np.random.default_rng(5).standard_normal(
        ref.shape), jnp.float32).transpose(0, 2, 1, 3)

    def loss_flash(qt, kt, vt):
        return jnp.sum(
            fa._flash(qt, kt, vt, causal, 8, 8, True, t)[0][:, :, :t, :]
            * w[:, :, :t, :])

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal)
                       * w[:, :, :t, :].transpose(0, 2, 1, 3))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(qt, kt, vt)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        gf = np.asarray(gf[:, :, :t, :].transpose(0, 2, 1, 3))
        np.testing.assert_allclose(gf, np.asarray(gd),
                                   rtol=1e-4, atol=1e-4, err_msg=f"d{name}")
        # Padded rows must carry zero gradient.
    for gf in g_flash:
        np.testing.assert_allclose(np.asarray(gf[:, :, t:, :]), 0.0,
                                   atol=1e-6)


def test_sharded_matches_dense():
    mesh = build_mesh({"data": 2, "model": 2, "seq": 1})
    attn = make_flash_attention(mesh, block_q=8, block_k=8)
    q, k, v = _qkv(np.random.default_rng(4), b=4, h=4)

    @jax.jit
    def run(q, k, v):
        return attn(q, k, v, True)

    with jax.set_mesh(mesh):
        out = run(q, k, v)
    ref = dense_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_bf16_forward_close():
    q, k, v = _qkv(np.random.default_rng(5))
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, False, block_q=8, block_k=8)
    ref = dense_attention(q, k, v, False)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


def test_pallas_call_present_in_tpu_lowering():
    """Dump-based proof the flagship attention IS the Pallas kernel: the
    TPU cross-platform lowering of a flash-attention program contains the
    Mosaic custom call (dense attention lowers to plain dot/softmax ops)."""
    attn = make_flash_attention(interpret=False)  # compiled-kernel path
    q = jnp.zeros((2, 256, 4, 64), jnp.float32)
    traced = jax.jit(lambda q, k, v: attn(q, k, v, True)).trace(q, q, q)
    txt = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in txt
    dense_txt = jax.jit(
        lambda q, k, v: dense_attention(q, k, v, True)).trace(
            q, q, q).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in dense_txt


def test_default_attention_resolves_by_backend():
    """Resolved at the first call, not at construction (building a model
    must not initialize a backend): dense on the CPU test backend."""
    from autodist_tpu.models import transformer

    assert transformer._resolve_default_attention() is dense_attention
    q, k, v = _qkv(np.random.RandomState(0), 2, 16, 2, 8)
    np.testing.assert_array_equal(
        transformer.default_attention()(q, k, v, True),
        dense_attention(q, k, v, True))

    from autodist_tpu.models.transformer_lm import transformer_lm

    spec = transformer_lm(vocab_size=64, num_layers=1, num_heads=2,
                          head_dim=8, d_ff=32, max_len=16)
    assert spec.config["vocab_size"] == 64  # factory accepts attn_fn=None


def test_meshless_flash_shards_over_the_trace_mesh_context():
    """The model-zoo default is built with no mesh.  Traced where a mesh
    context is set (a session's step sets its own), it must run the
    kernel per shard: in the TPU lowering the Mosaic custom call sees the
    per-device batch and heads, not the global ones GSPMD would gather."""
    import re

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    attn = make_flash_attention(interpret=False)
    sh = NamedSharding(mesh, P("data", None, "model"))
    q = jax.ShapeDtypeStruct((8, 256, 4, 64), jnp.float32, sharding=sh)

    def f(q, k, v):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return attn(q, k, v, True)

    txt = jax.jit(f).trace(q, q, q).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = [ln for ln in txt.splitlines() if "tpu_custom_call" in ln]
    assert calls
    for ln in calls:   # kernel layout is [B, H, T, D]: 8/4 x 4/2
        assert re.search(r"tensor<2x2x256x64xf32>", ln), ln
        assert "tensor<8x" not in ln, ln


def test_block_picker_prefers_tile_multiples():
    from autodist_tpu.ops.flash_attention import _pick_block

    assert _pick_block(4096, 512) == 512
    assert _pick_block(2176, 512) == 128   # 17*128: only 128-multiple divisor
    assert _pick_block(2048, 512) == 512
    assert _pick_block(24, 512) == 24      # tiny interpret-mode sequence
    assert _pick_block(8192, 512) == 512
