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


# ---------------------------------------------------------------------------
# the fused backward (one call, each score tile formed once)
# ---------------------------------------------------------------------------
def _module():
    """The module itself: ``autodist_tpu.ops.flash_attention`` as an
    attribute is the function of that name."""
    import importlib
    return importlib.import_module("autodist_tpu.ops.flash_attention")


def _grads(attn, q, k, v, w):
    return jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v) * w),
                    argnums=(0, 1, 2))(q, k, v)


_FUSED_CASES = {
    # name: (t, block_q, block_k, kv_len or None)
    "one_tile": (16, 16, 16, None),
    "four_blocks_a_side": (32, 8, 8, None),     # dQ sums over >= 3 k blocks
    "wide_q_tile": (32, 16, 8, None),
    "wide_k_tile": (32, 8, 16, None),
    "uneven_blocks": (24, 128, 128, None),      # falls back to a divisor
    "padded": (24, 8, 8, 21),                   # kv_len < T inside a block
    "padded_whole_block": (32, 8, 8, 24),
}


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("case", sorted(_FUSED_CASES))
def test_fused_backward_matches_dense(case, causal):
    fa = _module()
    t, bq, bk, kv_len = _FUSED_CASES[case]
    live = kv_len or t
    q, k, v = _qkv(np.random.default_rng(10), t=live, d=8)
    w = jnp.asarray(np.random.default_rng(11).standard_normal(q.shape),
                    jnp.float32)
    pad = [(0, 0), (0, 0), (0, t - live), (0, 0)]

    def flash(q, k, v):    # the private op on [B,H,T,D], padded by hand
        qt, kt, vt = (jnp.pad(x.transpose(0, 2, 1, 3), pad)
                      for x in (q, k, v))
        o = fa._flash(qt, kt, vt, causal, bq, bk, True, live)[0]
        return o[:, :, :live, :].transpose(0, 2, 1, 3)

    dense = lambda q, k, v: dense_attention(q, k, v, causal)  # noqa: E731
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    for gf, gd, name in zip(_grads(flash, q, k, v, w),
                            _grads(dense, q, k, v, w), "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=1e-4, atol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("causal", [False, True])
def test_fused_backward_lse_cotangent(causal):
    """A loss that uses lse: the dlse cotangent is folded into Δ, and the
    fused kernel must carry it into dQ AND dK (ring attention merges
    blocks through lse)."""
    from autodist_tpu.ops.flash_attention import flash_attention_with_lse

    q, k, v = _qkv(np.random.default_rng(12), t=32, d=8)
    w = jnp.asarray(np.random.default_rng(13).standard_normal(q.shape),
                    jnp.float32)
    u = jnp.asarray(np.random.default_rng(14).standard_normal((2, 2, 32)),
                    jnp.float32)

    def loss_flash(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal, block_q=8,
                                          block_k=8)
        return jnp.sum(o * w) + jnp.sum(lse * u)

    def loss_dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((32, 32), bool)), s, -1e30)
        lse = jax.nn.logsumexp(s, axis=-1)
        return jnp.sum(dense_attention(q, k, v, causal) * w) + jnp.sum(
            lse * u)

    np.testing.assert_allclose(loss_flash(q, k, v), loss_dense(q, k, v),
                               rtol=1e-5)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=1e-4, atol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("blocks", [(8, 8), (8, 16), (16, 8)])
def test_unmasked_tiles_agree_with_all_masked(blocks):
    """Tiles wholly below the diagonal take the unmasked path.  With one
    32 x 32 tile every score goes through the mask; with 8- and 16-blocks
    most tiles skip it: outputs and gradients must be the same."""
    q, k, v = _qkv(np.random.default_rng(15), t=32, d=8)
    w = jnp.asarray(np.random.default_rng(16).standard_normal(q.shape),
                    jnp.float32)
    bq, bk = blocks
    tiled = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, True, block_q=bq, block_k=bk)
    whole = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, True, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(tiled(q, k, v)),
                               np.asarray(whole(q, k, v)),
                               rtol=1e-6, atol=1e-6)
    for gt, gw, name in zip(_grads(tiled, q, k, v, w),
                            _grads(whole, q, k, v, w), "qkv"):
        np.testing.assert_allclose(np.asarray(gt), np.asarray(gw),
                                   rtol=1e-5, atol=1e-5, err_msg=f"d{name}")


# ---------------------------------------------------------------------------
# products at the stated precision (operands rounded to bfloat16)
# ---------------------------------------------------------------------------
def _round_bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@jax.custom_vjp
def _rounded_operand(x):
    return _round_bf16(x)


_rounded_operand.defvjp(lambda x: (_round_bf16(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _rounded_result(y):
    return y


_rounded_result.defvjp(lambda y: (y, None), lambda _, g: (_round_bf16(g),))


def _mm_bf16(eq, a, b):
    """``benchmark/reference/gpt2.py: _mm`` under "bfloat16": both
    operands rounded, forward and backward (the cotangent of the result is
    an operand of the backward products)."""
    return _rounded_result(jnp.einsum(eq, _rounded_operand(a),
                                      _rounded_operand(b)))


def _dense_attention_bf16_products(q, k, v, causal):
    t = q.shape[1]
    s = _mm_bf16("bqhd,bkhd->bhqk", q, k) / (q.shape[-1] ** 0.5)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
    return _mm_bf16("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def _rel_err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("causal", [False, True])
def test_rounded_operands_match_rounded_dense(causal):
    """The compiled kernel's path (every product operand rounded to
    bfloat16, float32 sums and statistics), run under the interpreter
    through the private static argument, against a dense attention that
    rounds the way the benchmark's reference does.  The two round the
    probabilities at different moments (before and after the division by
    their sum), so they agree to a few 1e-3, and each stands further from
    unrounded attention than from the other."""
    fa = _module()
    q, k, v = _qkv(np.random.default_rng(17), t=64, d=16)
    w = jnp.asarray(np.random.default_rng(18).standard_normal(q.shape),
                    jnp.float32)

    def flash(q, k, v):
        qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        o = fa._flash(qt, kt, vt, causal, 16, 16, True, 64, jnp.bfloat16)[0]
        return o.transpose(0, 2, 1, 3)

    rounded = lambda q, k, v: _dense_attention_bf16_products(  # noqa: E731
        q, k, v, causal)
    exact = lambda q, k, v: dense_attention(q, k, v, causal)  # noqa: E731
    got = (flash(q, k, v),) + _grads(flash, q, k, v, w)
    want = (rounded(q, k, v),) + _grads(rounded, q, k, v, w)
    full = (exact(q, k, v),) + _grads(exact, q, k, v, w)
    for g, r, e, name in zip(got, want, full, ("o", "dq", "dk", "dv")):
        assert _rel_err(g, r) < 6e-3, (name, _rel_err(g, r))
        assert 1e-3 < _rel_err(g, e) < 2e-2, (name, _rel_err(g, e))


def test_product_operand_resolves_from_what_is_observable():
    """bfloat16 when compiled, float32 under the interpreter or when jax
    is asked for ``highest`` / ``float32`` products; nothing else decides."""
    fa = _module()
    assert fa._product_operand(True) == jnp.float32
    assert fa._product_operand(False) == jnp.bfloat16
    for asked in ("highest", "float32"):
        with jax.default_matmul_precision(asked):
            assert fa._product_operand(False) == jnp.float32
    with jax.default_matmul_precision("bfloat16"):
        assert fa._product_operand(False) == jnp.bfloat16


def test_grad_lowers_to_two_pallas_calls_with_float32_boundaries():
    """One attention under ``jax.grad`` is TWO Mosaic calls on the TPU
    (forward, fused backward; it was three), and float32 inputs cross
    both boundaries as float32: the rounding to bfloat16 happens inside
    the kernel, which is what the benchmark's
    ``product_operands_narrower_than_stated`` reads off these lines."""
    import re

    attn = make_flash_attention(interpret=False)
    q = jnp.zeros((2, 256, 4, 64), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v, True))

    txt = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, q, q).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = [ln for ln in txt.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2, len(calls)
    for ln in calls:
        types = re.findall(r"tensor<[0-9x]*x([a-z]+[0-9]+)>", ln)
        assert types and set(types) == {"f32"}, ln


# ---------------------------------------------------------------------------
# keys wider than values (latent attention: Dk 192, Dv 128; here 24 / 16)
# ---------------------------------------------------------------------------
_WIDE_KEYS = {
    # name: (t, block_q, block_k)
    "one_tile": (16, 16, 16),
    "four_blocks_a_side": (32, 8, 8),
    "padded_to_a_tile": (21, 8, 8),        # the public op pads T to 24
}


def _qk_wider_than_v(seed, t, dk=24, dv=16):
    rng = np.random.default_rng(seed)
    q, k = (jnp.asarray(rng.standard_normal((2, t, 2, dk)), jnp.float32)
            for _ in range(2))
    v, w = (jnp.asarray(rng.standard_normal((2, t, 2, dv)), jnp.float32)
            for _ in range(2))
    u = jnp.asarray(rng.standard_normal((2, 2, t)), jnp.float32)
    return q, k, v, w, u


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("case", sorted(_WIDE_KEYS))
def test_keys_wider_than_values_match_dense(case, causal):
    """``q, k: [B,T,H,24]``, ``v: [B,T,H,16]`` -> ``[B,T,H,16]``: the
    forward, ``lse`` (scaled by 1/sqrt(24)), and all three gradients of a
    loss that uses both outputs, against dense attention."""
    from autodist_tpu.ops.flash_attention import flash_attention_with_lse

    t, bq, bk = _WIDE_KEYS[case]
    q, k, v, w, u = _qk_wider_than_v(20, t)

    def dense_lse(q, k):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
        return jax.nn.logsumexp(s, axis=-1)

    def loss_flash(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal, block_q=bq,
                                          block_k=bk)
        return jnp.sum(o * w) + jnp.sum(lse * u)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal) * w) + jnp.sum(
            dense_lse(q, k) * u)

    o, lse = flash_attention_with_lse(q, k, v, causal, block_q=bq,
                                      block_k=bk)
    assert o.shape == v.shape and lse.shape == (2, 2, t)
    np.testing.assert_allclose(o, dense_attention(q, k, v, causal),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(lse, dense_lse(q, k), rtol=2e-5, atol=2e-5)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        assert gf.shape == gd.shape
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=1e-4, atol=1e-4, err_msg=f"d{name}")


def test_vmem_estimate_counts_both_widths():
    """At one width of 64 the limit stays Mosaic's default, as before the
    kernels took two widths (the gpt2 cell compiles what it compiled); at
    192 / 128 over 4096 rows the backward asks for the 40 MiB Mosaic
    wanted when the estimate stood at 38."""
    fa = _module()

    def limit(t, dk, dv, block=512):
        return fa._compiler_params(
            t, block, [(dk, 4)] * 4 + [(dv, 4)] * 2
            + [(dk, 2), (dv, 2), (dk, 4)],
            [(dk, 4)] * 4 + [(dv, 4)] * 4, (dk, dv)).vmem_limit_bytes

    assert limit(1024, 64, 64) is None
    assert limit(4096, 192, 128) >= 40.1 * 2 ** 20
    assert fa._row_bytes((64, 4), (192, 2)) == 128 * 4 + 256 * 2


# ---------------------------------------------------------------------------
# the forward's output and row statistics carry names (PR 28)
# ---------------------------------------------------------------------------
def _names_in(jaxpr):
    from autodist_tpu.models.routed_decoder import equations

    return [eqn.params["name"] for eqn in equations(jaxpr)
            if eqn.primitive.name == "name"]


@pytest.mark.parametrize("with_lse", [False, True])
def test_residual_names_are_inert_without_a_policy(with_lse, monkeypatch):
    """A plain ``jax.grad`` through the tagged forward, the lse cotangent
    included, gives the bits it gives with the tags taken out."""
    from autodist_tpu.ops.flash_attention import flash_attention_with_lse

    fa = _module()
    q, k, v, w, u = _qk_wider_than_v(30, 32)

    def grad():   # a new function each time: jax keeps traces by function
        def loss(q, k, v):
            if not with_lse:
                return jnp.sum(flash_attention(q, k, v, True, block_q=8,
                                               block_k=8) * w)
            o, lse = flash_attention_with_lse(q, k, v, True, block_q=8,
                                              block_k=8)
            return jnp.sum(o * w) + jnp.sum(lse * u)

        return jax.value_and_grad(loss, argnums=(0, 1, 2))

    tagged = grad()(q, k, v)
    assert _names_in(jax.make_jaxpr(grad())(q, k, v).jaxpr) \
        == list(fa.RESIDUAL_NAMES)
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    assert _names_in(jax.make_jaxpr(grad())(q, k, v).jaxpr) == []
    for got, want in zip(jax.tree.leaves(tagged),
                         jax.tree.leaves(grad()(q, k, v))):
        np.testing.assert_array_equal(got, want)


def test_a_policy_that_keeps_the_names_holds_o_and_lse_and_no_q_k_v(capsys):
    """Under ``save_only_these_names(*RESIDUAL_NAMES)`` a checkpoint that
    projects q, k, v itself keeps its arguments, the kernel's ``o``
    ``[B,H,T,Dv]`` and ``lse``: neither projection (they are recomputed),
    and with them in hand the backward has no forward kernel to run."""
    fa = _module()
    x = jnp.asarray(np.random.default_rng(40).standard_normal((2, 32, 2, 8)),
                    jnp.float32)
    wk = jnp.ones((8, 24)) / 8
    wv = jnp.ones((8, 16)) / 8

    def f(x, wk, wv):
        return jnp.sum(flash_attention(x @ wk, jnp.sin(x) @ wk, x @ wv, True,
                                       block_q=8, block_k=8) ** 2)

    kept = jax.checkpoint(
        f, policy=jax.checkpoint_policies.save_only_these_names(
            *fa.RESIDUAL_NAMES))
    jax.ad_checkpoint.print_saved_residuals(kept, x, wk, wv)
    held = [ln for ln in capsys.readouterr().out.splitlines()
            if "from the argument" not in ln]
    assert len(held) == 2, held
    assert held[0].startswith("f32[2,2,32,16] ")           # o, kernel layout
    assert "flash_attention.py" in held[0]
    assert held[1].startswith(f"f32[2,2,32,1] named '{fa.RESIDUAL_NAMES[1]}'")

    def kernels(fn):
        return str(jax.make_jaxpr(jax.grad(fn))(x, wk, wv)).count(
            "pallas_call")

    assert kernels(kept) == kernels(f) == 2
    assert kernels(jax.checkpoint(f)) == 3


# ---------------------------------------------------------------------------
# grouped-query heads and a selection of keys (PR 32)
# ---------------------------------------------------------------------------

def _grouped_dense(q, k, v, mask):
    """Plain masked softmax: query head j reads key/value head j // group;
    ``mask [B, Tq, Tk]``."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(mask[:, None], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def test_sixteen_heads_over_two_at_a_width_of_256():
    """The Qwen3-Next full layer's heads (issue 40): 16 query heads over 2
    key/value heads of 256, forward and gradients against the plain
    softmax; each key/value head's gradient is the sum of its eight query
    heads'."""
    rng = np.random.default_rng(40)
    q, k, v = _grouped_qkv(rng, 1, 64, 16, 2, d=256)
    mask = jnp.tril(jnp.ones((64, 64), bool))[None]
    w = jnp.asarray(rng.standard_normal((1, 64, 16, 256)), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, True, block_q=32, block_k=32,
                               interpret=True)

    np.testing.assert_allclose(flash(q, k, v), _grouped_dense(q, k, v, mask),
                               rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_grouped_dense(*a, mask) * w),
                    (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=2e-5)


def test_vmem_estimate_at_a_width_of_256():
    """By ``_compiler_params``' own arithmetic the backward at 256 wide and
    float32 in holds 8 KB a row: 8,192 rows ask for what 16,384 rows of
    128 ask for (the keye and smallthinker cells run that), within what
    the v5e leaves a kernel; 16,384 rows at 256 would be the whole VMEM,
    and the limit stops at 100 MiB."""
    fa = _module()

    def limit(t, d, block=512):
        return fa._compiler_params(
            t, block, [(d, 4)] * 4 + [(d, 4)] * 2
            + [(d, 2), (d, 2), (d, 4)],
            [(d, 4)] * 4 + [(d, 4)] * 4, (d, d)).vmem_limit_bytes

    assert limit(8192, 256) - (8 << 20) == pytest.approx(
        limit(16384, 128) - (8 << 20), rel=0.1)
    assert 64 << 20 < limit(8192, 256) < 100 << 20
    assert limit(16384, 256) == 100 << 20


def _grouped_qkv(rng, b, t, heads, kv_heads, d=16):
    return tuple(jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
                 for h in (heads, kv_heads, kv_heads))


def _top_k_rows(rng, b, t, topk):
    """A causal selection as a learned top-k gives it: row ``r`` keeps the
    ``topk`` largest of ``r + 1`` random scores, all of them where it has
    no more."""
    score = np.where(np.tril(np.ones((t, t), bool)),
                     rng.standard_normal((b, t, t)), -np.inf)
    kth = np.sort(score, -1)[..., ::-1][..., min(topk, t) - 1][..., None]
    return jnp.asarray((score >= kth) & np.isfinite(score))


@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("causal", [False, True])
def test_grouped_query_heads_match_dense(heads, kv_heads, causal):
    """Forward and gradients where ``heads // kv_heads`` query heads share
    a key/value head: the index map repeats, dK and dV are the group's
    sums."""
    rng = np.random.default_rng(heads + kv_heads)
    q, k, v = _grouped_qkv(rng, 2, 48, heads, kv_heads)
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((48, 48), bool)) if causal
                            else jnp.ones((48, 48), bool), (2, 48, 48))
    w = jnp.asarray(rng.standard_normal((2, 48, heads, 16)), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal, block_q=16, block_k=16,
                               interpret=True)

    np.testing.assert_allclose(flash(q, k, v), _grouped_dense(q, k, v, mask),
                               rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_grouped_dense(*a, mask) * w),
                    (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("t,topk,block", [
    (64, 16, 32),     # every q block holds a row that selects
    (128, 64, 32),    # two q blocks end before topk: the causal tiles
    (128, 48, 32),    # topk inside a q block
    (64, 64, 32),     # T at topk: the last row is the first to select
    (96, 200, 32),    # T under topk: the selection is the causal triangle
    (128, 32, 64),    # two words a key block
])
def test_selected_keys_match_plain_masked_attention(t, topk, block):
    """Grouped heads attending only to the keys chosen for each row,
    forward and in the fused backward, against the plain masked softmax."""
    from autodist_tpu.ops.flash_attention import pack_selection

    rng = np.random.default_rng(t + topk)
    q, k, v = _grouped_qkv(rng, 2, t, 4, 2)
    mask = _top_k_rows(rng, 2, t, topk)
    assert int(mask[0, -1].sum()) == min(topk, t)
    words = pack_selection(mask, block_k=block)
    w = jnp.asarray(rng.standard_normal((2, t, 4, 16)), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, True, block_q=block, block_k=block,
                               interpret=True, selection=words,
                               select_from=topk)

    np.testing.assert_allclose(flash(q, k, v), _grouped_dense(q, k, v, mask),
                               rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_grouped_dense(*a, mask) * w),
                    (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5)


def test_selection_is_refused_where_it_cannot_be_honoured():
    from autodist_tpu.ops.flash_attention import pack_selection

    q, k, v = _grouped_qkv(np.random.default_rng(0), 1, 64, 2, 2)
    words = pack_selection(jnp.tril(jnp.ones((1, 64, 64), bool)),
                           block_k=32)
    with pytest.raises(ValueError, match="selection is causal"):
        flash_attention(q, k, v, False, interpret=True, selection=words,
                        select_from=8)
    with pytest.raises(ValueError, match="select_from"):
        flash_attention(q, k, v, True, interpret=True, selection=words)
    with pytest.raises(ValueError, match="query heads over"):
        flash_attention(q, k[:, :, :1].repeat(3, 2)[:, :, :3], v, True,
                        interpret=True)
    with pytest.raises(ValueError, match="multiple of 32"):
        pack_selection(jnp.ones((1, 48, 48), bool), block_k=16)


# ---------------------------------------------------------------------------
# a window (PR 36): query t attends to keys t - window < s <= t
# ---------------------------------------------------------------------------

def _window_mask(b, t, window):
    pos = np.arange(t)
    return jnp.broadcast_to(jnp.asarray(
        (pos[None, :] <= pos[:, None])
        & (pos[None, :] > pos[:, None] - window)), (b, t, t))


@pytest.mark.parametrize("t,window,blocks,heads,kv_heads", [
    (128, 48, (16, 16), 4, 2),     # three tiles wide, grouped heads
    (128, 50, (16, 16), 2, 2),     # no multiple of the tile
    (128, 16, (16, 16), 2, 1),     # one tile: the edge crosses the diagonal's
    (128, 7, (16, 16), 2, 2),      # shorter than a tile
    (128, 1, (32, 32), 2, 2),      # the query's own position alone
    (96, 96, (32, 32), 4, 2),      # the sequence's length: nothing is cut
    (96, 500, (32, 32), 2, 2),     # longer than the sequence
    (128, 40, (32, 16), 4, 1),     # q blocks of two key blocks
    (128, 40, (16, 32), 4, 1),     # key blocks of two q blocks
    (100, 24, (16, 16), 4, 2),     # padded to 104: blocks of 8
    (200, 77, (16, 16), 2, 1),     # padded, grouped, no multiple
])
def test_window_matches_plain_masked_attention(t, window, blocks, heads,
                                               kv_heads):
    """Forward and fused backward under a window against the masked dense
    formula: tiles behind the window skipped, the edge and the diagonal
    masked."""
    rng = np.random.default_rng(t + window)
    q, k, v = _grouped_qkv(rng, 2, t, heads, kv_heads)
    mask = _window_mask(2, t, window)
    w = jnp.asarray(rng.standard_normal((2, t, heads, 16)), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, True, block_q=blocks[0],
                               block_k=blocks[1], interpret=True,
                               window=window)

    np.testing.assert_allclose(flash(q, k, v), _grouped_dense(q, k, v, mask),
                               rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_grouped_dense(*a, mask) * w),
                    (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5)


def test_window_differs_from_causal_and_from_another_window():
    q, k, v = _grouped_qkv(np.random.default_rng(5), 1, 128, 2, 2)
    out = {w: flash_attention(q, k, v, True, block_q=16, block_k=16,
                              interpret=True, window=w)
           for w in (None, 32, 64)}
    # rows inside every window are the causal rows
    np.testing.assert_allclose(out[32][:, :32], out[None][:, :32],
                               rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(out[32][:, 32:] - out[None][:, 32:]).max()) > 1e-2
    assert float(jnp.abs(out[32][:, 64:] - out[64][:, 64:]).max()) > 1e-2


@pytest.mark.parametrize("t,block,window,tiles", [
    (16384, 512, 4096, 252),     # the cell's: 36 + 24 x 9 of the 528
    (16384, 512, None, 528),
    (16384, 512, 16384, 528),
    (16384, 512, 2048, 10 + 28 * 5),
    (128, 16, 48, 1 + 2 + 3 + 5 * 4),
    (128, 16, 50, 1 + 2 + 3 + 4 + 4 * 5),
])
def test_pairs_computed_counts_the_tiles_the_window_visits(t, block, window,
                                                           tiles):
    from autodist_tpu.ops.flash_attention import pairs_computed

    assert pairs_computed(t, block_q=block, block_k=block, window=window) \
        == tiles * block * block


def test_window_visits_the_tiles_it_counts():
    """The forward's loops run over the key blocks ``pairs_computed``
    counts and no other: the score products of one head, counted through
    the kernel's own loop bounds by poisoning what lies behind the window
    (a key block the kernel visited would turn the output NaN)."""
    from autodist_tpu.ops.flash_attention import pairs_computed

    t, window, block = 128, 40, 16
    q, k, v = _grouped_qkv(np.random.default_rng(9), 1, t, 2, 2)
    clean = flash_attention(q, k, v, True, block_q=block, block_k=block,
                            interpret=True, window=window)
    # rows of the last q block see key blocks (112 - 39) // 16 = 4 onward
    poison = jnp.arange(t)[None, :, None, None] < 4 * block
    k_bad, v_bad = (jnp.where(poison, jnp.nan, x) for x in (k, v))
    dirty = flash_attention(q, k_bad, v_bad, True, block_q=block,
                            block_k=block, interpret=True, window=window)
    assert np.isfinite(np.asarray(dirty[:, -block:])).all()
    np.testing.assert_allclose(dirty[:, -block:], clean[:, -block:],
                               rtol=1e-6, atol=1e-6)
    assert pairs_computed(t, block_q=block, block_k=block, window=window) \
        == (1 + 2 + 3 + 5 * 4) * block * block


def test_window_is_refused_where_it_cannot_be_honoured():
    from autodist_tpu.ops.flash_attention import pack_selection

    q, k, v = _grouped_qkv(np.random.default_rng(0), 1, 64, 2, 2)
    with pytest.raises(ValueError, match="a window is causal"):
        flash_attention(q, k, v, False, interpret=True, window=16)
    with pytest.raises(ValueError, match="a window is causal"):
        flash_attention(q, k, v, True, interpret=True, window=0)
    words = pack_selection(jnp.tril(jnp.ones((1, 64, 64), bool)),
                           block_k=32)
    with pytest.raises(ValueError, match="without a selection"):
        flash_attention(q, k, v, True, interpret=True, window=16,
                        selection=words, select_from=8)
    attn = make_flash_attention(interpret=True, window=16, block_q=16,
                                block_k=16)
    np.testing.assert_allclose(
        attn(q, k, v, True), _grouped_dense(q, k, v, _window_mask(1, 64, 16)),
        rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# block diffusion (PR 47): a sequence and its noised copy under a
# block-structured mask that is not causal
# ---------------------------------------------------------------------------

def _block_diffusion_mask(block, length, rows):
    """The four rules, pair by pair."""
    mask = np.zeros((rows, rows), bool)
    for r in range(rows):
        for c in range(rows):
            if r < length and c < length:
                mask[r, c] = c // block <= r // block
            elif r >= length and c < length:
                mask[r, c] = c // block < (r - length) // block
            elif r >= length:
                mask[r, c] = (c - length) // block == (r - length) // block
    return mask


@pytest.mark.parametrize("length,block,blocks,heads,kv_heads,copies", [
    (128, 4, (32, 32), 2, 2, 2),     # the cell's rule at a test's size
    (128, 1, (32, 32), 8, 1, 2),     # blocks of one; heads 8 : 1
    (128, 128, (64, 64), 2, 1, 2),   # one block: the sequence's length
    (192, 4, (128, 128), 4, 2, 2),   # L no multiple of the tile: tiles of 96
    (96, 4, (32, 32), 2, 1, 1),      # the clean rows alone: block-causal
    (60, 30, (16, 32), 2, 2, 2),     # no power of two, wider than a
                                     # tile; tiles of 15 and 30
])
def test_block_diffusion_matches_plain_masked_attention(
        length, block, blocks, heads, kv_heads, copies):
    """Forward and fused backward under the block-diffusion mask against
    the masked dense formula: outputs and all three gradients."""
    from autodist_tpu.ops.flash_attention import block_diffusion_mask

    t = copies * length
    rng = np.random.default_rng(length + block)
    q, k, v = _grouped_qkv(rng, 2, t, heads, kv_heads)
    mask = _block_diffusion_mask(block, length, t)
    np.testing.assert_array_equal(
        np.asarray(block_diffusion_mask(block, length, t)), mask)
    mask = jnp.broadcast_to(jnp.asarray(mask), (2, t, t))
    w = jnp.asarray(rng.standard_normal((2, t, heads, 16)), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, False, block_q=blocks[0],
                               block_k=blocks[1], interpret=True,
                               block_diffusion=(block, length))

    np.testing.assert_allclose(flash(q, k, v), _grouped_dense(q, k, v, mask),
                               rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_grouped_dense(*a, mask) * w),
                    (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("length,block,tile", [
    (64, 4, 16),      # the edge crosses one tile a q block
    (64, 32, 16),     # blocks of two tiles
])
def test_block_diffusion_visits_the_tiles_it_counts(length, block, tile):
    """The tiles the kernels visit are the tiles with a pair the mask lets
    through, no more and no fewer, and ``pairs_computed`` counts them.
    Forward: a key block turned NaN poisons the q blocks that visit it.
    Backward: a q block's cotangent turned NaN poisons dK of the key
    blocks whose program visits it."""
    from autodist_tpu.ops.flash_attention import pairs_computed

    t, n = 2 * length, 2 * length // tile
    bd = (block, length)
    mask = _block_diffusion_mask(block, length, t)
    needed = mask.reshape(n, tile, n, tile).any(axis=(1, 3))   # [qb, kb]
    assert pairs_computed(t, block_q=tile, block_k=tile,
                          block_diffusion=bd) == needed.sum() * tile * tile
    q, k, v = _grouped_qkv(np.random.default_rng(7), 1, t, 2, 1)

    @jax.jit
    def flash(q, k, v):
        return flash_attention(q, k, v, False, block_q=tile, block_k=tile,
                               interpret=True, block_diffusion=bd)

    rows = jnp.arange(t)[None, :, None, None] // tile
    by_block = lambda x: np.isnan(np.asarray(x)).reshape(  # noqa: E731
        n, -1).any(axis=1)
    forward = np.stack([by_block(flash(
        q, *(jnp.where(rows == kb, jnp.nan, x) for x in (k, v))))
        for kb in range(n)], axis=1)
    np.testing.assert_array_equal(forward, needed)
    d_k = jax.jit(lambda ct: jax.vjp(flash, q, k, v)[1](ct)[1])
    backward = np.stack([by_block(d_k(
        jnp.where(rows == qb, jnp.nan, jnp.ones((1, t, 2, 16)))))
        for qb in range(n)], axis=0)
    np.testing.assert_array_equal(backward, needed)


def test_pairs_computed_at_the_cells_size_and_refusals():
    from autodist_tpu.ops.flash_attention import pairs_computed

    # 8,192 tokens, blocks of 4, tiles of 512: 136 tiles a head for the
    # clean q blocks, 136 + 16 for the noised ones; the causal kernel over
    # the 16,384 rows visits 528
    assert pairs_computed(16384, block_diffusion=(4, 8192)) \
        == 288 * 512 * 512
    assert pairs_computed(8192, block_diffusion=(4, 8192)) == 136 * 512 * 512
    assert pairs_computed(16384) == 528 * 512 * 512
    # one block: every clean tile, every noised tile, none between
    assert pairs_computed(256, block_q=32, block_k=32,
                          block_diffusion=(128, 128)) == 2 * 16 * 32 * 32
    q, k, v = _grouped_qkv(np.random.default_rng(0), 1, 64, 2, 2)
    for kw in (dict(causal=True, block_diffusion=(4, 32)),
               dict(causal=False, block_diffusion=(4, 32), window=8),
               dict(causal=False, block_diffusion=(5, 32)),    # 5 ∤ 32
               dict(causal=False, block_diffusion=(4, 48))):   # 64 rows
        with pytest.raises(ValueError, match="block diffusion is not"):
            flash_attention(q, k, v, interpret=True, **kw)
    # compiled, L has to need no padding (interpreted, any L does)
    with pytest.raises(ValueError, match="needs no padding"):
        flash_attention(*_grouped_qkv(np.random.default_rng(0), 1, 400, 2, 2),
                        False, interpret=False, block_diffusion=(4, 200))
