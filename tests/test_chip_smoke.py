"""chip_smoke.py's phase functions at a tiny size on the CPU mesh, the
command's refusal to pass without a chip, and the two small repairs that
came with it (the compile-cache helper; the peaks table is in
test_metrics.py)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

# The serving tests' model and engine geometry (test_serving_scheduler.py,
# test_serving_server.py): the paged and slot programs live in module-scope
# jit caches, so the shapes compiled here are not compiled again there.
TINY_LM = dict(vocab_size=61, num_layers=2, num_heads=2, head_dim=8,
               d_ff=32, max_len=48, seq_len=16)
TINY_SIZES = dict(p=16, prefix=16, tails=(3, 5), long=20, mid=10,
                  n=(4, 5, 6, 7))      # prompt buckets 8, 16 and 32 only
TINY_ENGINE = dict(slots=2, window=32, block_size=8, num_blocks=24, chunk=4)


def test_kernels_phase_interpreted():
    chip_smoke.kernels_phase(
        flash=[((1, 16, 2, 8), "float32", True, 1e-5),
               ((1, 16, 2, 12, 8), "float32", True, 1e-5)],
        matmul_shapes=[(8, 64, 128)], bucket_elems=8192,
        paged=dict(slots=2, heads=2, head_dim=8, block_size=8,
                   blocks_per_slot=2),
        interpret=True)


@pytest.fixture(scope="module")
def lm():
    return chip_smoke.make_lm(TINY_LM, jnp.float32)


def test_train_phase_on_cpu_mesh(lm):
    facts = chip_smoke.train_phase(
        *lm, strategy="AllReduce", mesh_axes={"data": 4}, batch_size=8,
        steps=2)
    assert facts["mesh"] == {"data": 4}
    assert facts["losses"][-1] < facts["losses"][0]


def test_train_moe_phase_returns_the_counts():
    facts = chip_smoke.train_moe_phase(
        dict(vocab_size=61, num_layers=2, d_model=32, num_heads=2,
             qk_nope=8, qk_rope=4, v_head=8, kv_lora=16, d_ff=48,
             d_expert=12, num_experts=16, experts_held=(4, 4), top_k=3,
             seq_len=16), batch_size=4, steps=3)
    assert facts["losses"][-1] < facts["losses"][0]
    assert len(facts["tokens_per_expert"]) == 1
    assert 0 < facts["share_of_picks_here"] <= 1
    # the CPU's default attention is dense and tags nothing; the one
    # routed layer keeps its picks: 4 sequences x 16 tokens x 3 x int32
    kept = facts["remat_kept_bytes"]
    assert kept["flash_attention/o"] == 0
    assert kept["routed_moe/chosen"] == kept["routed_moe/order"] == 768
    # a router forced onto the four held experts takes every chunk of the
    # sorted order, the seeded one the first alone; both are the layer
    # written out
    budgets = facts["chunks"]
    assert budgets["rungs"] == [1024, 2048]     # whole 512-row tiles
    assert budgets["forced"]["rows_routed_here"] == 512 * 3
    assert budgets["forced"]["rows_covered"] == 2048
    assert budgets["even"]["rows_covered"] == 1024
    assert max(budgets[r][gap] for r in ("even", "forced")
               for gap in ("gap", "gradient_gap")) < 1e-5


def test_train_dsa_moe_phase_returns_counts_and_pairs():
    facts = chip_smoke.train_dsa_moe_phase(
        dict(vocab_size=61, num_layers=2, d_model=32, num_heads=4,
             num_kv_heads=2, head_dim=16, index_heads=2, index_dim=8,
             topk=32, d_expert=12, num_experts=16, experts_held=(4, 4),
             top_k=3, seq_len=64, block_k=32, index_rows=32, moe_slice=64),
        batch_size=2, steps=2)
    assert all(np.isfinite(facts["losses"]))
    assert len(facts["tokens_per_expert"]) == 2
    # two layers x two sequences x (32 x 33 / 2 + 32 x 32) selected pairs
    assert facts["pairs_per_step"] == {"selected": 4 * 1552,
                                       "computed": 4 * 64 * 64}
    # the kernel (interpreted here) against the plain form: the same words
    select = facts["select_against_the_plain_form"]
    assert select["bits_differing"] == select["rows_differing"] == 0
    assert select["first_places"] == []


def test_train_swa_moe_phase_checks_both_kinds_of_layer():
    facts = chip_smoke.train_swa_moe_phase(
        dict(vocab_size=61, num_layers=2, d_model=32, num_heads=4,
             num_kv_heads=2, head_dim=16, window=24, window_layout=(0, 1),
             rope_layout=(0, 1), d_expert=12, num_experts=16,
             experts_held=(4, 4), top_k=3, seq_len=64, block_k=32,
             moe_slice=64),
        batch_size=2, steps=2, tol=1e-4, block_q=32, block_k=32)
    assert all(np.isfinite(facts["losses"]))
    assert len(facts["tokens_per_expert"]) == 2
    assert set(facts["rows_against_the_plain_formula"]) == {
        "global_attn", "window_attn"}
    # 2 sequences x 4 heads x (64 x 65 / 2 + 24 x 25 / 2 + 40 x 24) pairs
    # attended; off the TPU attention is dense and the gauge counts the
    # kernel's tiles all the same: one q block against 2 + 2 key blocks
    assert facts["pairs_per_step"] == {
        "attended": 8 * (2080 + 300 + 960), "computed": 8 * 2 * 64 * 64}
    with pytest.raises(AssertionError, match="one global and one window"):
        chip_smoke.train_swa_moe_phase(
            dict(vocab_size=61, num_layers=2, d_model=32, num_heads=4,
                 num_kv_heads=2, head_dim=16, window=24,
                 window_layout=(1, 1), rope_layout=(1, 1), d_expert=12,
                 num_experts=16, seq_len=64, block_k=32, moe_slice=64),
            batch_size=1, steps=1, tol=1e-4)


def test_train_gdn_moe_phase_checks_the_recurrence_and_steps_the_model():
    model = dict(vocab_size=61, num_layers=2, d_model=32, full_interval=2,
                 linear_key_heads=2, linear_value_heads=4, linear_head_dim=8,
                 num_heads=4, num_kv_heads=2, head_dim=16, rotary_dim=4,
                 d_expert=12, d_shared=12, num_experts=16,
                 experts_held=(4, 4), top_k=3, seq_len=64, chunk=16,
                 block_k=32, moe_slice=64)
    facts = chip_smoke.train_gdn_moe_phase(model, batch_size=2, steps=3,
                                           tol=1e-4)
    assert len(facts["losses"]) == 3 and all(np.isfinite(facts["losses"]))
    assert len(facts["tokens_per_expert"]) == 2
    # o and five gradients, at both precisions (a CPU's are the same)
    gaps = facts["recurrence_against_token_by_token"]
    assert set(gaps) == {"default", "highest"}
    assert all(len(v) == 6 and max(v) < 1e-4 for v in gaps.values())
    # q, k, v, z and four gradients of the convolution's kernel pair
    conv = facts["conv_against_the_plain_lines"]["rel_err"]
    assert len(conv) == 8 and max(conv) < 1e-5
    # one linear layer x 2 x 64 tokens x 4 value heads
    per = facts["pairs_per_step"]
    assert per["recurrence"] == 7 * 8 * 8 * 512
    assert per["computed"] >= per["recurrence"]
    with pytest.raises(AssertionError, match="one linear and one full"):
        chip_smoke.train_gdn_moe_phase(dict(model, full_interval=4),
                                       batch_size=1, steps=1, tol=1e-4)


def test_train_sconv_moe_phase_checks_the_convolution_and_steps_the_model():
    model = dict(vocab_size=61, layer_types=("conv", "full_attention"),
                 num_dense_layers=1, d_model=32, num_heads=4, num_kv_heads=2,
                 head_dim=8, d_ff=48, d_expert=12, num_experts=16,
                 experts_held=(4, 4), top_k=3, seq_len=64, block_k=32,
                 moe_slice=64)
    facts = chip_smoke.train_sconv_moe_phase(model, batch_size=2, steps=3,
                                             tol=1e-5)
    assert len(facts["losses"]) == 3 and all(np.isfinite(facts["losses"]))
    # the dense layer has no experts: one layer's counts
    assert len(facts["tokens_per_expert"]) == 1
    assert max(facts["gated_conv_against_rolled_sum"]["gaps"]) < 1e-5
    # one attention layer x 2 sequences x 4 heads
    assert facts["pairs_per_step"] == {"causal": 8 * 64 * 65 // 2,
                                       "computed": 8 * 64 * 64}
    with pytest.raises(AssertionError, match="one dense conv layer"):
        chip_smoke.train_sconv_moe_phase(
            dict(model, layer_types=("conv", "conv")), batch_size=1,
            steps=1, tol=1e-5)


def test_serve_phases_over_http(lm):
    facts = chip_smoke.serve_paged_phase(
        *lm, sizes=TINY_SIZES, engine=TINY_ENGINE)
    assert facts["requests"] == 8
    assert facts["trie_hit_blocks"] == 1 + 1 + 2   # twins, shared prefix
    slots = chip_smoke.serve_slots_phase(
        *lm, sizes=TINY_SIZES, engine=dict(slots=2, window=24, chunk=4))
    assert slots["completed"] == 2
    exact = chip_smoke.serve_exact_phase(
        *lm, sizes=TINY_SIZES, engine=TINY_ENGINE)
    assert exact["token_exact"] == exact["of"] == 2   # float32 on the CPU


def test_pallas_call_shapes_reads_compiled_hlo():
    hlo = ('  %fwd.3 = (f32[8,6,2048,64]{3,2,1,0}, f32[8,6,2048,1]{3,2,1,0})'
           ' custom-call(f32[8,6,2048,64]{3,2,1,0} %a), '
           'custom_call_target="tpu_custom_call"\n'
           '  %other = f32[4]{0} custom-call(), custom_call_target="x"\n')
    assert chip_smoke.pallas_call_shapes(hlo) == [
        [("f32", (8, 6, 2048, 64)), ("f32", (8, 6, 2048, 1))]]


def test_command_refuses_the_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_compile_cache_helper(monkeypatch):
    from autodist_tpu.utils.compile_cache import place_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert place_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO, ".jax_cache")
        assert place_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
