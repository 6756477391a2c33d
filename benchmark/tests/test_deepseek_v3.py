"""What the kanana-2-30b-a3b share brings to the benchmark: its cell's
files, ``flops_mla_moe.py`` against a count by hand, the new reference
deciding ``correct`` at test size (a sound run, the timed path broken
underneath, the controls), and the new readers against a run that has
nothing for them."""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark.run as brun
from benchmark import flops_mla_moe as flops
from benchmark import harness
from benchmark.entries import train

CELL = "train.kanana-2-30b-a3b.ep8-share.seq4096"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "data_deepseek")
TINY = "train.deepseek-v3-tiny.cpu"
NEW_READERS = ("mla_attention_roofline", "mla_moe_train_mfu_pct",
               "moe_routed_device_pct", "moe_rows_computed_per_routed_row")


def drive(cell, seed=2**31 + 7, seconds=1.0):
    return brun.run_cell(cell, seed, seconds, False,
                         jax.devices()[:cell.chips], time.perf_counter())


# ---------------------------------------------------------------------------
# the cell and its configuration
# ---------------------------------------------------------------------------

def test_the_cell_finds_its_files_and_reports_its_rows():
    cell = harness.Cell(CELL)
    assert cell.config["reference"] == "kanana_2_30b_a3b_ep8_share"
    assert callable(train.reference_module(cell.config).train_steps)
    assert cell.traffic == {**cell.traffic, "kind": "lm_batches",
                            "seq_len": 4096, "global_batch": 4}
    assert set(cell.workload["limits"]) == {
        "loss_gap_max", "first_grad_norm_gap_worst_leaf",
        "param_change_norm_gap_worst_leaf", "first_grad_sample_rel_err"}
    rows = {m["name"] for m in cell.metric_rows("per_layer")}
    assert set(NEW_READERS) <= rows
    assert {"compile_s", "compiles_in_window", "train_step_ms_p50",
            "device_idle_pct.train", "hbm_peak_gb.train"} <= rows
    assert not {"train_mfu_pct", "flash_attention_roofline"} & rows
    # the gpt2 cell reports none of the new rows
    old = {m["name"] for m in harness.Cell(
        "train.gpt2-medium.1chip").metric_rows("per_layer")}
    assert not set(NEW_READERS) & old and "compile_s" in old


def test_every_width_is_the_sources_and_the_cut_is_stated():
    cfg = harness.Cell(CELL).config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == cfg["source"])
        differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differ == set(cfg["reduced"])
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == 8
    assert dep["experts_held"] == [0, cfg["n_routed_experts"]] == [0, 16]
    assert dep["n_routed_experts_published"] == 128
    assert cfg["vocab_size"] * 8 == dep["vocab_size_published"]
    kw = cfg["program"]["kwargs"]
    assert (kw["d_model"], kw["num_heads"], kw["qk_nope"], kw["qk_rope"],
            kw["v_head"], kw["kv_lora"], kw["d_ff"], kw["d_expert"],
            kw["num_experts"], kw["top_k"], kw["shared_experts"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
        cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["intermediate_size"],
        cfg["moe_intermediate_size"], 128, cfg["num_experts_per_tok"],
        cfg["n_shared_experts"])
    assert kw["routed_scale"] == cfg["routed_scaling_factor"]
    assert kw["vocab_size"] == cfg["vocab_size"]
    assert kw["num_layers"] == cfg["num_hidden_layers"]


def test_flops_against_a_count_by_hand():
    cfg = harness.Cell(CELL).config
    # W_q 2048 x 32 x 192, W_kva 2048 x 576, W_kvb 512 x 32 x 256,
    # W_o 32 x 128 x 2048
    attn = 12_582_912 + 1_179_648 + 4_194_304 + 8_388_608
    assert flops.attention_params(cfg) == attn == 26_345_472
    assert flops.expert_params(cfg) == 3 * 2048 * 768 == 4_718_592
    assert flops.held_share(cfg) == 0.125
    # an expert layer: two shared experts, the router's 128 outputs, and
    # 6 x 1/8 of an expert a token
    sparse = 2 * 4_718_592 + 262_144 + 0.75 * 4_718_592
    matmul = 5 * attn + 3 * 2048 * 6144 + 4 * sparse + 16032 * 2048
    assert flops.matmul_params_per_token(cfg) == pytest.approx(matmul)
    assert matmul / 1e6 == pytest.approx(255.26, abs=0.01)
    # what the program's init makes, to the parameter
    spec = train.build_spec(cfg)
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    assert flops.total_params(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(shapes)) == 575_955_968
    # 6 FLOPs a parameter + 5 layers x 3 x 4096 x 32 heads x (192 + 128)
    per_token = flops.train_flops_per_token(cfg, 4096)
    assert per_token == pytest.approx(6 * matmul + 5 * 3 * 4096 * 32 * 320)
    assert per_token / 1e9 == pytest.approx(2.1607, abs=1e-3)
    assert flops.routed_flops_per_token(cfg) / per_token == pytest.approx(
        0.0393, abs=1e-3)


def test_flash_call_with_two_widths():
    # [4, 32, 4096] at Dk 192, Dv 128, float32
    square = 4 * 32 * 4096 * 4096
    f, b = flops.flash_call(4, 32, 4096, 192, 128, 4, backward=False)
    assert f == square * 320 and b == 4 * 32 * 4096 * 4 * (384 + 256)
    f, b = flops.flash_call(4, 32, 4096, 192, 128, 4, backward=True)
    assert f == square * (3 * 192 + 2 * 128)
    assert b == 4 * 32 * 4096 * 4 * (4 * 192 + 4 * 128)
    # one width: benchmark/flops.py's count
    from benchmark import flops as gpt2_flops

    for backward in (False, True):
        assert flops.flash_call(4, 16, 1024, 64, 64, 4, backward=backward) \
            == gpt2_flops.flash_call(4, 16, 1024, 64, 4, backward=backward)
    # at these widths the chip's FLOPs bind, not its bytes
    assert f / 197e12 > b / 819e9


# ---------------------------------------------------------------------------
# ``correct`` with the new reference, at test size
# ---------------------------------------------------------------------------

def tiny_cell():
    return harness.Cell(TINY, root=DATA)


def test_sound_run_is_correct(capsys):
    line = drive(tiny_cell())
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_s_chip", "setup_s"}
    out = capsys.readouterr().out
    for name in ("loss_gap_max", "product_operands_narrower_than_stated",
                 "first_grad_norm_gap_worst_leaf",
                 "param_change_norm_gap_worst_leaf",
                 "first_grad_sample_rel_err"):
        assert f"check {name}:" in out


class _Frozen:
    """A session whose step computes its loss and returns its state
    unchanged."""

    def __init__(self, sess):
        self._sess = sess

    def run(self, batch):
        return self._sess.evaluate(batch)

    def __getattr__(self, name):
        return getattr(self._sess, name)


class _HalfBatch(_Frozen):
    """A session whose step leaves out the second half of the batch."""

    def run(self, batch):
        half = batch["tokens"][:batch["tokens"].shape[0] // 2]
        return self._sess.run({"tokens": np.concatenate([half, half])})


def _absent_expert(monkeypatch):
    """The expert layer computing only the first three of the four experts
    it holds: a token's pick of the fourth is dropped."""
    import importlib

    from autodist_tpu.parallel import moe

    # (the package exports the factory under the module's name)
    mla_moe_lm = importlib.import_module("autodist_tpu.models.mla_moe_lm")

    def short(params, x, *, experts_held, **kwargs):
        first, count = experts_held
        fewer = dict(params, experts=jax.tree_util.tree_map(
            lambda a: a[:count - 1], params["experts"]))
        y, sizes = moe.routed_moe_ffn(
            fewer, x, experts_held=(first, count - 1), **kwargs)
        return y, jnp.pad(sizes, (0, 1))

    monkeypatch.setattr(mla_moe_lm, "routed_moe_ffn", short)


@pytest.mark.parametrize("broken,failing", [
    (_Frozen, "param_change_norm_gap_worst_leaf"),
    (_HalfBatch, "loss_gap_max"),
    (None, "first_grad_sample_rel_err"),     # a held expert left out
])
def test_broken_step_is_not_correct(monkeypatch, capsys, broken, failing):
    if broken is None:
        _absent_expert(monkeypatch)
    else:
        build = train.build_session
        monkeypatch.setattr(train, "build_session",
                            lambda *a, **k: broken(build(*a, **k)))
    line = drive(tiny_cell())
    assert line["correct"] is False
    out = capsys.readouterr().out
    bad = [ln for ln in out.splitlines() if "NOT CORRECT" in ln]
    assert any(failing in ln for ln in bad), out


@pytest.mark.parametrize("compute", ["bfloat16", "int8"])
def test_control_leaves_the_tolerance(compute):
    """The reference in a lower precision in the program's place, judged
    as the program is, fails one of the cell's numbers on every seed."""
    from benchmark import traffic, weights

    cell = tiny_cell()
    ref = train.reference_module(cell.config)
    spec = train.build_spec(cell.config)
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    failed = 0
    for seed in (1, 2, 3):
        batches = traffic.lm_batches(cell.traffic, 61, seed)
        check = [jnp.asarray(next(batches)) for _ in range(3)]
        p0 = ref.to_reference(weights.make_weights(shapes, seed))
        want = ref.train_steps(p0, check, row_block=4, sample_seed=seed)
        ctl = ref.train_steps(p0, check, row_block=4, compute=compute,
                              sample_seed=seed)
        pooled, _ = train.sample_errors(ctl[3], want[3])
        numbers = {
            "product_operands_narrower_than_stated":
                train.narrow_product_operands(
                    ref.lowered_block_grad(p0, check[0][:2], compute),
                    "float32"),
            "first_grad_sample_rel_err": pooled,
            "loss_gap_max": max(abs(a - b)
                                for a, b in zip(ctl[0], want[0])),
            "first_grad_norm_gap_worst_leaf":
                train.worst_leaf_gap(ctl[1], want[1]),
            "param_change_norm_gap_worst_leaf":
                train.worst_leaf_gap(ctl[2], want[2])}
        limits = dict(cell.workload["limits"],
                      product_operands_narrower_than_stated=0)
        failed += any(numbers[k] > limits[k] for k in numbers)
    assert failed == 3


def test_the_reference_keeps_to_plain_jax():
    """No sort, no grouped product, no kernel, nothing of the program."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "reference", "deepseek_v3.py")) as f:
        code = f.read().split('"""', 2)[2]
    for word in ("autodist_tpu", "ragged_dot", "pallas", "argsort",
                 "jnp.sort", "lax.sort", "top_k("):
        assert word not in code, word


# ---------------------------------------------------------------------------
# the new readers where there is nothing to read
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_readers_return_none_on_a_gpt2_run(metric):
    """An untraced run of a GPT-2 cell against a program registry that
    holds no expert gauges: every new reader returns None and raises
    nothing (the parent commit's side of a traced run)."""
    from autodist_tpu.telemetry import registry

    registry.reset_for_testing()
    cell = harness.Cell("train.gpt2-tiny.cpu", root=os.path.join(
        os.path.dirname(DATA), "data"))
    run = harness.Run(cell, 1, 1.0, True, jax.devices()[:1],
                      time.perf_counter())
    run.counters.update(steps=3, tokens_per_step=256, step_s=[0.1] * 3,
                        seq_len=64, global_batch=4)
    run.peaks = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    assert harness._load_reader(harness.Cell(CELL), metric)(run) is None
