"""The flash kernels, and the latent attention around them, compiled for a
TPU v5e that is described, not attached.

Interpret mode cannot see what Mosaic refuses (a lane index it cannot prove
aligned, a transpose of an odd shape, more VMEM than a kernel may hold), so
the forward and the fused backward are compiled here at the widths the
repo runs them at, at no chip time.  Nothing runs: this says nothing about
results or speed.  The topology is described inside a fixture (only the
worker that is given this file loads the TPU's library), and every such
test lives in this one file.
"""
import collections
import functools
import math
import os
import re
import typing

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from autodist_tpu.ops.flash_attention import flash_attention_with_lse


@pytest.fixture(scope="module")
def chips():
    """The four described devices of a v5e 2x2 host."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but not read back: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(chips):
    return SingleDeviceSharding(chips[0])


_SHAPES = {
    # [B, T, H, D], type, causal
    "gpt2_medium_train": ((4, 1024, 16, 64), jnp.float32, True),
    "chip_smoke_lm": ((8, 2048, 12, 64), jnp.bfloat16, True),
    "full_mask": ((2, 512, 4, 64), jnp.float32, False),
    "one_short_block": ((2, 100, 4, 64), jnp.float32, True),   # pads to 104
    "padded_blocks": ((2, 1000, 4, 64), jnp.bfloat16, False),  # pads to 1024
    "uneven_blocks": ((1, 2176, 2, 64), jnp.float32, True),    # 17 x 128
    "long_wide": ((1, 8192, 8, 128), jnp.bfloat16, True),  # raised VMEM limit
    # keys wider than values ([B, T, H, Dk, Dv]): latent attention at the
    # kanana cell's widths, one sequence (the model's call) and the batch
    "mla_one_sequence": ((1, 4096, 32, 192, 128), jnp.float32, True),
    "mla_batch": ((4, 4096, 32, 192, 128), jnp.float32, True),
    "mla_padded": ((2, 1000, 4, 192, 128), jnp.float32, False),
}


# bfloat16 operands (the default) everywhere; float32 operands under
# ``highest`` at every size something may ask them at
_CASES = [(name, precision) for name in sorted(_SHAPES)
          for precision in ("default", "highest")
          if (name, precision) != ("long_wide", "highest")]


@pytest.mark.parametrize("name,precision", _CASES)
def test_forward_and_fused_backward_compile(one_chip, name, precision):
    shape, dtype, causal = _SHAPES[name]
    x = jax.ShapeDtypeStruct(shape[:4], dtype, sharding=one_chip)
    v = jax.ShapeDtypeStruct(shape[:3] + shape[-1:], dtype,
                             sharding=one_chip)

    def loss(q, k, v):   # both outputs used: the lse cotangent path too
        o, lse = flash_attention_with_lse(q, k, v, causal, interpret=False)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(lse)

    with jax.default_matmul_precision(precision):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            x, x, v).compile()
    assert len(_pallas_calls(compiled)) == 2     # forward, fused backward


def _pallas_calls(compiled):
    return [ln for ln in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln]


def _reader_pattern(metric):
    """``KERNEL`` of ``benchmark/metrics/<metric>.py``: the name and shape
    its reader finds the Pallas call by in a trace."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(metric, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.KERNEL


_GROUPED = {
    # [B, T, H, Hkv, D], the selection's topk (None: grouped heads alone)
    "keye_one_sequence": ((1, 16384, 32, 4, 128), 2048),
    "selected_short": ((1, 4096, 8, 2, 128), 2048),
    "selected_batch": ((2, 2048, 4, 1, 64), 512),
    "grouped_alone": ((2, 2048, 16, 4, 64), None),
    # the Qwen3-Next full layer (issue 40): 16 heads over 2 of 256 wide at
    # 8,192 tokens, float32 in: K and V resident, 8 KB a row in the backward
    "qwen3_next_one_sequence": ((1, 8192, 16, 2, 256), None),
    # the LFM2-8B-A1B attention layer (issue 43): gpt2's head width 64 with
    # keye's grouping of heads for the first time together, 32 over 8 at
    # 8,192 tokens, float32 in
    "lfm2_one_sequence": ((1, 8192, 32, 8, 64), None),
}


@pytest.mark.parametrize("name,precision", [
    (name, precision) for name in sorted(_GROUPED)
    for precision in ("default", "highest")
    if (name, precision) not in (("keye_one_sequence", "highest"),
                                 ("qwen3_next_one_sequence", "highest"),
                                 ("lfm2_one_sequence", "highest"))])
def test_grouped_heads_and_selection_compile(one_chip, name, precision):
    """The kernels with ``H // Hkv`` query heads a key/value head and a
    packed selection, at the keye cell's 16,384 tokens (float32 in, 64 MiB
    of VMEM asked for the backward) and at shorter ones: value and
    gradient, one forward and one fused backward call."""
    from autodist_tpu.ops import flash_attention

    (b, t, h, g, d), topk = _GROUPED[name]

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    args = [shape(b, t, h, d), shape(b, t, g, d), shape(b, t, g, d)]
    if topk:
        args.append(shape(b, t // 32, t, dtype=jnp.int32))

    def loss(q, k, v, words=None):
        selection = {} if words is None else dict(selection=words,
                                                  select_from=topk)
        return jnp.sum(flash_attention(q, k, v, True, interpret=False,
                                       **selection))

    with jax.default_matmul_precision(precision):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            *args).compile()
    calls = _pallas_calls(compiled)
    assert len(calls) == 2
    # keys and values go in with their own heads: nothing was repeated
    assert all(f"f32[{b},{g},{t},{d}]" in ln for ln in calls)


_SELECTS = {
    # T, index heads x width, topk, the attention's key block
    "keye_one_sequence": (16384, 16, 64, 2048, 512),
    "chip_smoke_sequence": (4096, 16, 64, 2048, 512),
    "tile_across_topk": (1536, 4, 64, 640, 512),   # rows 512..767 of a tile
    "one_tile": (384, 2, 128, 100, 128),            # 384 rows: no 256 tile
}


@pytest.mark.parametrize("name", sorted(_SELECTS))
def test_dsa_select_compiles(one_chip, name):
    """``ops/index_select.py`` at the keye cell's shapes (16,384 tokens, 16
    index heads of 64, 2,048 of a row's keys, key blocks of 512: a tile's
    ``[16384, 256]`` scores, the keys and their three terms and the tile's
    operands in 56 MiB of VMEM) and at shorter ones: ONE kernel, by the
    name ``dsa_index_select_device_pct`` finds it under, that reads
    float32 operands and writes the words and the tiles' flags."""
    from autodist_tpu.ops import index_select

    t, heads, dim, topk, bk = _SELECTS[name]

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    compiled = jax.jit(functools.partial(
        index_select.dsa_select, topk=topk, block_k=bk, interpret=False)
    ).lower(shape(t, heads, dim), shape(t, dim), shape(t, heads)).compile()
    calls = _pallas_calls(compiled)
    assert len(calls) == 1
    tiles = t // index_select.tile_of(t)
    assert re.match(rf"\s*(ROOT )?%dsa_select[.\d]* = \(s32\[{t // 32},{t}\]"
                    rf".*, s32\[{tiles}\]", calls[0]), calls[0]
    # float32 in (cut into bfloat16 terms in VMEM), written twice abreast
    assert f"f32[{heads},{t},{2 * dim}]" in calls[0]
    assert f"f32[{t},{2 * dim}]" in calls[0] and "bf16[" not in calls[0]


_WINDOWED = {
    # [B, T, H, Hkv, D], window
    "smallthinker_one_sequence": ((1, 16384, 28, 4, 128), 4096),
    "window_short": ((2, 2048, 8, 2, 128), 640),     # no multiple of 512
    "window_in_a_tile": ((1, 4096, 4, 4, 64), 100),   # crosses the diagonal's
    "window_padded": ((2, 1000, 4, 1, 64), 300),      # pads to 1024
}


@pytest.mark.parametrize("name,precision", [
    (name, precision) for name in sorted(_WINDOWED)
    for precision in ("default", "highest")
    if (name, precision) != ("smallthinker_one_sequence", "highest")])
def test_window_compiles(one_chip, name, precision):
    """The kernels under a static window at the smallthinker cell's shape
    (28 query heads over 4 key/value heads of 128, 16,384 tokens, a window
    of 4,096, float32 in) and at shorter ones whose window ends inside a
    tile: value and gradient, one forward and one fused backward call, the
    keys and values with their own heads."""
    import importlib

    flash_attention = importlib.import_module(
        "autodist_tpu.ops.flash_attention")
    (b, t, h, g, d), window = _WINDOWED[name]

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention.flash_attention(
            q, k, v, True, interpret=False, window=window))

    with jax.default_matmul_precision(precision):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            shape(b, t, h, d), shape(b, t, g, d), shape(b, t, g, d)
        ).compile()
    calls = _pallas_calls(compiled)
    assert len(calls) == 2
    tp = flash_attention._pad_len(t, False)
    assert all(f"f32[{b},{g},{tp},{d}]" in ln for ln in calls)


_BLOCK_DIFFUSION = {
    # [B, T, H, Hkv, D], (block, L): T is L or 2 L
    "sdar_one_sequence": ((1, 16384, 32, 4, 128), (4, 8192)),
    "blocks_short": ((2, 1024, 8, 2, 128), (4, 512)),
    "blocks_of_three": ((1, 1536, 4, 1, 64), (3, 768)),   # tiles of 384
    "one_block": ((1, 2048, 4, 2, 128), (1024, 1024)),
    "clean_alone": ((2, 2048, 4, 4, 64), (4, 2048)),      # block-causal
}


@pytest.mark.parametrize("name,precision", [
    (name, precision) for name in sorted(_BLOCK_DIFFUSION)
    for precision in ("default", "highest")
    if (name, precision) != ("sdar_one_sequence", "highest")])
def test_block_diffusion_compiles(one_chip, name, precision):
    """The kernels under the block-diffusion mask at the sdar cell's shape
    (32 query heads over 4 key/value heads of 128, a sequence of 8,192
    tokens and its noised copy, blocks of 4, float32 in) and at shorter
    ones (blocks of no power of two, one block, the clean rows alone):
    value and gradient, one forward and one fused backward call, the keys
    and values with their own heads.  What Mosaic has to take here and
    interpret mode cannot show: the rule's ``[bk, 1]`` and ``[1, bq]``
    integer vectors and their division."""
    import importlib

    flash_attention = importlib.import_module(
        "autodist_tpu.ops.flash_attention")
    (b, t, h, g, d), bd = _BLOCK_DIFFUSION[name]

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention.flash_attention(
            q, k, v, False, interpret=False, block_diffusion=bd))

    with jax.default_matmul_precision(precision):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            shape(b, t, h, d), shape(b, t, g, d), shape(b, t, g, d)
        ).compile()
    calls = _pallas_calls(compiled)
    assert len(calls) == 2
    assert all(f"f32[{b},{g},{t},{d}]" in ln for ln in calls)


def test_latent_attention_writes_each_kernel_operand_once(one_chip):
    """One layer's latent attention at the kanana cell's widths, value and
    gradient, four sequences mapped under the layer's checkpoint as
    ``mla_moe_lm`` runs them.  The mechanism of PR 30 is static, so its
    guard is this compile and no runtime counter: XLA's own count of bytes
    (8.29e9 with the weights' products sliced after they were written and
    the rotary turn on a minor dimension of two; 7.24e9 as shipped), and
    no ``slice_bitcast`` or ``pad`` fusion that writes an activation of
    32 MB or more to the chip's main memory (what is left by those names
    joins a weight's gradient once a layer, or lives in the fast memory,
    ``S(1)``, and takes microseconds)."""
    from autodist_tpu.models.mla_moe_lm import (
        KEPT_NAMES,
        attention_operands,
        latent_attention,
    )
    from autodist_tpu.ops import flash_attention

    d, heads, nope, rope, dv, latent, t = 2048, 32, 128, 64, 128, 512, 4096

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    leaves = {"wq": shape(d, heads, nope + rope),
              "wkv_a": shape(d, latent + rope),
              "kv_norm": {"scale": shape(latent)},
              "wkv_b": shape(latent, heads, nope + dv),
              "wo": shape(heads, dv, d)}

    @functools.partial(
        jax.checkpoint, prevent_cse=False,
        policy=jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES))
    def one_sequence(p, row):
        return latent_attention(
            p, row[None], functools.partial(flash_attention, interpret=False),
            theta=1e6, eps=1e-6)[0]

    def loss(p, x, cotangent):
        p = attention_operands(p, nope)
        return jnp.vdot(jax.lax.map(lambda row: one_sequence(p, row), x),
                        cotangent)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        leaves, shape(4, t, d), shape(4, t, d)).compile()
    assert len(_pallas_calls(compiled)) == 2
    assert compiled.cost_analysis()["bytes accessed"] <= 7.3e9
    written = re.compile(
        r"^\s*(?:ROOT )?%((?:slice_bitcast|pad_)[\w.\-]*) = "
        r"\(?f32\[([\d,]+)\]\{([^}]*)\}", re.M)
    for name, dims, layout in written.findall(compiled.as_text()):
        dims = tuple(map(int, dims.split(",")))
        assert not (t in dims and "S(1)" not in layout
                    and 4 * math.prod(dims) >= 32e6), (name, dims, layout)


def test_data_parallel_over_four_chips_compiles(chips):
    """The model-zoo default under a ``data=4`` mesh (the dp4 training
    cell's attention): ``shard_map`` hands each chip its 4 of 16 rows, and
    the kernels compile on [4, 16, 1024, 64] a chip."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from autodist_tpu.ops import make_flash_attention

    mesh = Mesh(np.array(chips), ("data",))
    attn = make_flash_attention(mesh, interpret=False)
    x = jax.ShapeDtypeStruct((16, 1024, 16, 64), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v, True))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    calls = _pallas_calls(compiled)
    assert len(calls) == 2
    # by the name and the shape ``flash_attention_roofline`` reads a traced
    # dp4 run by: a rename of the call fails here, not a reader in silence
    found = re.compile(_reader_pattern("flash_attention_roofline").format(
        4, 16, 1024, 64))
    for ln in calls:
        assert "f32[4,16,1024,64]" in ln and "f32[16," not in ln, ln
        assert ln.strip().startswith("%shard_map") and found.search(
            ln.strip()), ln


class _ExpertLayer(typing.NamedTuple):
    """An expert cell's routed layer as its model calls it, and its step."""
    #: ``routed_moe_ffn``'s arguments: picks a token, the router
    call: dict
    #: the layer's width, its experts held and in all
    d_model: int
    held: int
    total: int
    #: ``init_routed_moe_params``' further arguments (the shared experts'
    #: width among them)
    init: dict
    #: GB of the layer's value and gradient compiled for this chip AT THE
    #: PARENT OF PR 39 (the same 16,384 tokens as four calls of 4,096 under
    #: ``lax.map``, a ladder of three row budgets a call), which one call
    #: over all of them may not pass (but smallthinker's by one expert
    #: leaf, 0.063 GB: without the gathers' fill pass XLA holds one more
    #: copy of a leaf at the layer's peak; its whole STEP reads 11.10 GB for
    #: the parent's 12.08); lfm2's and qwen3-next's are the compiler's
    #: reading AT THE PARENT OF PR 44 (``jax.lax.ragged_dot`` for the
    #: grouped products)
    parent_gb: float
    #: the configuration and the sequences of a step
    config: str
    rows: int
    #: the experts' width, and the step's tokens in the slices the model
    #: hands the layer
    d_expert: int = 768
    slices: tuple = (4, 4096)


_EXPERT_LAYERS = {
    "kanana": _ExpertLayer(
        call=dict(top_k=6, scoring="sigmoid", routed_scale=2.448),
        d_model=2048, held=16, total=128,
        init=dict(selection_bias=True, d_shared=2 * 768), parent_gb=2.340,
        config="kanana-2-30b-a3b.ep8-share", rows=4),
    "keye": _ExpertLayer(
        call=dict(top_k=8, scoring="softmax"), d_model=2048, held=16,
        total=128, init=dict(selection_bias=False), parent_gb=2.656,
        config="keye-vl-2.0-30b-a3b.ep8-share", rows=1),
    "smallthinker": _ExpertLayer(
        call=dict(top_k=6, scoring="softmax_of_picked",
                  activation=jax.nn.relu), d_model=2560, held=8, total=64,
        init=dict(selection_bias=False), parent_gb=2.196 + 0.063,
        config="smallthinker-21b-a3b.ep8-share", rows=1),
    "lfm2": _ExpertLayer(
        call=dict(top_k=4, scoring="sigmoid", norm_eps=1e-6), d_model=2048,
        held=8, total=32, init=dict(selection_bias=True), parent_gb=2.899,
        config="lfm2-8b-a1b.ep4-share", rows=4, d_expert=1792,
        slices=(8, 4096)),
    "qwen3_next": _ExpertLayer(
        call=dict(top_k=10, scoring="softmax"), d_model=2048, held=32,
        total=512, init=dict(selection_bias=False, d_shared=512,
                             shared_gate=True), parent_gb=2.350,
        config="qwen3-next-80b-a3b.ep16-share", rows=2, d_expert=512),
}
#: what a v5e chip gives one program (``memory_stats()["bytes_limit"]``,
#: read on the chip in PR 28)
_BYTES_LIMIT = 16_909_336_064


def _computations(text):
    """``{name: its instructions}`` of compiled HLO text."""
    found, name = {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = line.split()[1 if line.startswith("ENTRY") else 0]
            found[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            found[name].append(line.strip())
    return found


def _need_gb(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes) / 1e9


def _on(chip, tree):
    return jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=chip), tree)


def _compiled_kernels(monkeypatch):
    """The routed layer's kernels, and the Gated DeltaNet mixer's
    convolution, as a TPU runs them, not interpreted."""
    from autodist_tpu.ops import gdn_conv, grouped_matmul, rows_to_tokens

    for module in (rows_to_tokens, grouped_matmul, gdn_conv):
        monkeypatch.setattr(module, "_use_interpret", lambda: False)


@pytest.mark.parametrize("cell", sorted(_EXPERT_LAYERS))
def test_expert_layer_compiles_as_one_call_over_chunks(one_chip, cell,
                                                       monkeypatch):
    """One routed expert layer at an expert cell's widths and ALL of a
    step's tokens in ONE call (handed in as slices of 4,096, under the
    layer's checkpoint that keeps the routing's integers), value and
    gradient: NO conditional is left in the compiled program; the loops
    over the further chunks survive as one ``while`` forward and one
    backward (XLA neither unrolled them nor lost one), each direction
    holds its three (forward) or nine (backward: the forward's three
    again, the rows' three cotangents by the kernel that reads the weights
    transposed, the weights' three gradients) grouped products twice, for
    the first chunk and in the loop's body, every one a kernel of
    ``ops/grouped_matmul.py`` (Mosaic took them at these widths and their
    VMEM) and no ``ragged-dot`` of XLA's left, and one kernel that brings
    the sorted rows back to token order in each; nothing the program
    computes is as large as every pick's row (``N * k * d``); and the
    compiler's reading of the layer's memory is no higher than at the
    parent."""
    from autodist_tpu.parallel import moe

    layer = _EXPERT_LAYERS[cell]
    call, d, held, total = layer.call, layer.d_model, layer.held, layer.total
    slices, slice_ = layer.slices
    _compiled_kernels(monkeypatch)
    keep = jax.checkpoint_policies.save_only_these_names(
        *moe.ROUTING_RESIDUAL_NAMES)
    picks = slices * slice_ * call["top_k"]
    chunk = moe.chunk_rows(picks, held, total, slice_ * call["top_k"])
    # twice the even load, which at kanana, keye and lfm2 is a slice's picks
    assert chunk == min(2 * picks * held // total, slice_ * call["top_k"])

    @functools.partial(jax.checkpoint, policy=keep)
    def one_call(params, x):
        return moe.routed_moe_ffn(params, x, experts_held=(0, held),
                                  train_router=False, **call)[0]

    def loss(params, x):
        y = one_call(params, x)
        return jnp.sum(y * y)

    params = jax.eval_shape(lambda: moe.init_routed_moe_params(
        jax.random.key(0), d, layer.d_expert, total, experts_held=held,
        **layer.init))
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        _on(one_chip, params),
        _on(one_chip, jax.ShapeDtypeStruct((slices, slice_, d), jnp.float32))
    ).compile()
    text = compiled.as_text()
    assert " conditional(" not in text and "ragged-dot" not in text
    computations = _computations(text)
    # by the name the program gave the kernel
    kernels = collections.Counter(
        (re.match(r"%([a-z_]+)[.\d]* = ", line).group(1), name)
        for name, lines in computations.items() for line in lines
        if 'custom_call_target="tpu_custom_call"' in line)
    bodies = set(re.findall(r" while\(.*body=([%\w.\-]+)", text))
    by_name = collections.Counter()
    in_loops = collections.defaultdict(collections.Counter)
    for (kernel, name), n in kernels.items():
        by_name[kernel] += n
        if name in bodies:
            in_loops[name][kernel] += n
    # the first chunk's and the loop's
    assert by_name == {"grouped_rows": 2 * (3 + 3), "grouped_rows_t": 2 * 3,
                       "grouped_weights": 2 * 3, "rows_to_tokens": 4}, by_name
    assert sorted(map(dict, in_loops.values()), key=len) == [
        {"grouped_rows": 3, "rows_to_tokens": 1},
        {"grouped_rows": 3, "grouped_rows_t": 3, "grouped_weights": 3,
         "rows_to_tokens": 1}], in_loops
    for lines in computations.values():
        for line in lines:
            if "=" not in line:
                continue
            result = line.split("=", 1)[1].split("(")[0]
            for dims in re.findall(r"\w+\[([\d,]+)\]", result):
                assert math.prod(map(int, dims.split(","))) < picks * d, line
    assert _need_gb(compiled) <= layer.parent_gb, _need_gb(compiled)


#: an expert cell's configuration and the sequences of its step; the sdar
#: cell's routed layer is the keye cell's to the number (PR 47)
_EXPERT_STEPS = {**{cell: (layer.config, layer.rows)
                    for cell, layer in _EXPERT_LAYERS.items()},
                 "sdar": ("sdar-30b-a3b-chat.ep8-share", 1)}


@pytest.mark.slow
@pytest.mark.parametrize("cell", sorted(_EXPERT_STEPS))
def test_expert_cell_step_fits_the_chip(one_chip, cell, monkeypatch):
    """The whole training step of an expert cell (the configuration's
    model at its own sizes, loss, gradient and AdamW, parameters and state
    donated) compiled for a described v5e: the compiler's reading of its
    memory is under what the chip gives a program.  ``slow`` (one mark on
    the parametrised test: a later cell's case inherits it): 80 to 155 s a
    cell, and what it asserts the benchmark holds ON THE CHIP in each of
    these cells on every PR (a step that does not fit gives its cell no
    result; ``hbm_peak_gb.train`` is in the ledger).  Run it, with
    ``-m slow``, after a change to a model's step that can raise its peak:
    it says so before a chip is asked."""
    import importlib
    import json

    import optax

    from autodist_tpu.ops.flash_attention import flash_attention

    config, rows = _EXPERT_STEPS[cell]
    _compiled_kernels(monkeypatch)
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            config + ".json")) as f:
        program = json.load(f)["program"]
    kwargs = dict(program["kwargs"])
    kwargs["dtype"] = getattr(jnp, kwargs["dtype"])
    module, factory = program["factory"].rsplit(".", 1)
    kernels = {"attn_fn": functools.partial(flash_attention, interpret=False)}
    if factory == "gdn_moe_lm":
        from autodist_tpu.ops.gated_delta_rule import gated_delta_rule

        kernels["gdn_fn"] = functools.partial(
            gated_delta_rule, chunk=kwargs["chunk"], interpret=False)
    spec = getattr(importlib.import_module(module), factory)(**kwargs,
                                                             **kernels)
    opt = optax.adamw(1e-3)
    shapes = jax.eval_shape(spec.init, jax.random.key(0))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, state, batch):
        loss, grads = jax.value_and_grad(spec.loss_fn)(params, batch)
        updates, state = opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state, loss

    compiled = step.lower(
        _on(one_chip, shapes), _on(one_chip, jax.eval_shape(opt.init, shapes)),
        {"tokens": jax.ShapeDtypeStruct((rows, kwargs["seq_len"]), jnp.int32,
                                        sharding=one_chip)}).compile()
    assert _need_gb(compiled) * 1e9 < _BYTES_LIMIT, _need_gb(compiled)


#: the compiler's reading of value and gradient of the rule alone at the
#: cell's shapes stays under this (0.685 GB with the prepared operands of
#: a segment in main memory, PR 41; 0.54 with none, PR 49)
_RULE_TEMPORARIES_GB = 0.6


def test_gated_delta_rule_and_its_backward_compile(one_chip):
    """Value and gradient of the gated delta rule at the qwen3-next cell's
    shapes (one sequence of 8,192 tokens, 16 key heads under 32 value
    heads of 128, chunk 64, the segment shipped) compiled for a described
    v5e, q, k, v handed a head's tokens one after the other as
    ``ops/gdn_conv.py`` writes them: ONE forward and ONE backward kernel in
    the text by name, each a segment a grid step within Mosaic's default
    VMEM (neither asks for more); under ``gdn_scan`` NO loop (the walk
    over the segments is the backward kernel's grid); no operand of the
    old scan (``[P, N, C, .]``: ``qg, w, u, kd, aqk``) and no copy,
    transpose or fusion as wide as q or v in main memory beside the
    kernels (the sums of ``dq, dk`` over a key head's two value heads are
    two plain ``reduce``); the call's temporaries under the limit above."""
    from autodist_tpu.ops import gated_delta_rule as gdr

    t, hk, hv, d, chunk = 8192, 16, 32, 128, 64

    def on(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def both(q, k, v, g, beta, do):
        by_token = functools.partial(jnp.swapaxes, axis1=1, axis2=2)
        o, pull = jax.vjp(lambda q, k, v, g, beta: by_token(
            gdr.gated_delta_rule(by_token(q), by_token(k), by_token(v), g,
                                 beta, chunk=chunk, interpret=False)),
            q, k, v, g, beta)
        return o, pull(do)

    compiled = jax.jit(both).lower(
        on(1, hk, t, d), on(1, hk, t, d), on(1, hv, t, d), on(1, t, hv),
        on(1, t, hv), on(1, hv, t, d)).compile()
    text = compiled.as_text()
    kernels = re.findall(r"%([\w.\-]+) = .*custom_call_target="
                         r"\"tpu_custom_call\"", text)
    assert sorted(name.split(".")[0] for name in kernels) == [
        gdr.KERNEL_NAME, gdr.BWD_KERNEL_NAME], kernels
    assert "vmem_limit_bytes" not in text
    assert not re.findall(r" while\(", text)
    p, n = hv, t // chunk
    assert not re.findall(rf"f32\[{p},{n},{chunk},({d}|{chunk})\]", text)
    wide = [line.split(" = ")[0].strip() for line in text.splitlines()
            if re.search(rf"= f32\[(1,)?({hk}|{hv}),({t},{d}|2,{t},{d})\]"
                         r".* (copy|transpose|fusion)\(", line)]
    assert not wide, wide
    assert compiled.memory_analysis().temp_size_in_bytes \
        < _RULE_TEMPORARIES_GB * 1e9


def test_gdn_conv_and_its_backward_compile(one_chip):
    """``ops/gdn_conv.py`` at the qwen3-next cell's shapes (one sequence of
    8,192 tokens, 16 key heads of 128 + 128 + 256 + 256 columns, four taps)
    compiled for a described v5e, value and cotangents: one kernel of each
    name in the text (Mosaic takes the windows' slices off the sublanes'
    grid and the blocks fit VMEM), and, handed ``qkvz`` a head's tokens one
    after the other as the projection writes it, nothing else in the
    program as wide as ``qkvz``: no copy into the kernels' layout."""
    from autodist_tpu.ops import gdn_conv

    t, hk, dl, share, taps = 8192, 16, 128, 2, 4

    def on(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def both(qkvz, w_q, w_k, w_v, *cotangents):
        out, pull = jax.vjp(
            lambda x, *w: gdn_conv.conv_silu_l2norm(
                jnp.swapaxes(x, 1, 2), *w, dl ** -0.5, interpret=False),
            qkvz, w_q, w_k, w_v)
        return out, pull(cotangents)

    text = jax.jit(both).lower(
        on(1, hk, t, 2 * dl * (1 + share)), on(hk, dl, taps),
        on(hk, dl, taps), on(hk, share * dl, taps), on(1, t, hk, dl),
        on(1, t, hk, dl), on(1, t, share * hk, dl),
        on(1, t, share * hk, dl)).compile().as_text()
    kernels = re.findall(r"%([\w.\-]+) = .*custom_call_target="
                         r"\"tpu_custom_call\"", text)
    assert sorted(name.split(".")[0] for name in kernels) == [
        gdn_conv.KERNEL_NAME, gdn_conv.BWD_KERNEL_NAME], kernels
    wide = [line for line in text.splitlines()
            if re.search(rf"= f32\[1,(16,8192|8192,16),{2 * dl * (1 + share)}\]"
                         r".* (copy|transpose|fusion)\(", line)]
    assert not wide, wide
