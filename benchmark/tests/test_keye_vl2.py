"""What the Keye-VL-2.0 share brings to the benchmark: its cell's files,
``flops_dsa_moe.py`` against a count by hand, the new reference deciding
``correct`` at test size (a sound run, the timed path broken underneath,
another selection in the program's place, the controls), and the new
readers against a run that has nothing for them and against a cut of the
cell's recorded trace."""
import importlib
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

import benchmark.run as brun
from benchmark import flops_dsa_moe as flops
from benchmark import harness, xplane
from benchmark.entries import train

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train.keye-vl-2.0-30b-a3b.ep8-share.seq16384"
DATA = os.path.join(HERE, "data_keye")
TINY = "train.keye-vl2-tiny.cpu"
CUT = os.path.join(HERE, "traces_scoped", CELL + ".cut.xplane.pb")
PEAKS = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
NEW_READERS = ("dsa_moe_train_mfu_pct", "sparse_attention_roofline",
               "dsa_index_select_device_pct",
               "dsa_pairs_computed_per_selected_pair")


def drive(cell, seed=2**31 + 7, seconds=1.0):
    return brun.run_cell(cell, seed, seconds, False,
                         jax.devices()[:cell.chips], time.perf_counter())


def reader(metric):
    return harness._load_reader(harness.Cell(CELL), metric)


# ---------------------------------------------------------------------------
# the cell and its configuration
# ---------------------------------------------------------------------------

def test_the_cell_finds_its_files_and_reports_its_rows():
    cell = harness.Cell(CELL)
    assert cell.config["reference"] == "keye_vl_2_0_30b_a3b_ep8_share"
    assert callable(train.reference_module(cell.config).train_steps)
    assert cell.traffic == {**cell.traffic, "kind": "lm_batches",
                            "seq_len": 16384, "global_batch": 1}
    assert set(cell.workload["limits"]) == {
        "loss_gap_max", "first_grad_norm_gap_worst_leaf",
        "param_change_norm_gap_worst_leaf", "first_grad_sample_rel_err"}
    rows = {m["name"] for m in cell.metric_rows("per_layer")}
    assert set(NEW_READERS) <= rows
    assert {"compile_s", "train_step_ms_p50", "device_idle_pct.train",
            "hbm_peak_gb.train", "moe_routed_device_pct",
            "moe_rows_computed_per_routed_row"} <= rows
    assert not {"train_mfu_pct", "flash_attention_roofline",
                "mla_attention_roofline", "mla_moe_train_mfu_pct",
                "collective_exposed_pct"} & rows
    assert {m["name"] for m in cell.metric_rows("end_to_end")} == {
        "train_tokens_s_chip", "setup_s"}
    for other in ("train.gpt2-medium.1chip",
                  "train.kanana-2-30b-a3b.ep8-share.seq4096"):
        assert not set(NEW_READERS) & {m["name"] for m in harness.Cell(
            other).metric_rows("per_layer")}


def test_every_width_is_the_sources_and_the_cut_is_stated():
    cfg = harness.Cell(CELL).config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == cfg["source"])
        differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differ == set(cfg["reduced"])
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "num_local_experts", "vocab_size"]
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == 8
    assert dep["experts_held"] == [0, cfg["num_experts"]] == [0, 16]
    assert dep["num_experts_published"] == 128
    assert cfg["vocab_size"] * 8 == dep["vocab_size_published"] == 151936
    kw, sa = cfg["program"]["kwargs"], cfg["sa_config"]
    assert (kw["d_model"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"], kw["index_heads"], kw["index_dim"], kw["topk"],
            kw["d_expert"], kw["num_experts"], kw["top_k"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"],
        cfg["moe_intermediate_size"], 128, cfg["num_experts_per_tok"])
    assert (kw["rope_theta"], kw["rms_eps"]) == (
        cfg["rope_theta"], cfg["rms_norm_eps"])
    assert kw["vocab_size"] == cfg["vocab_size"]
    assert kw["num_layers"] == cfg["num_hidden_layers"]
    assert kw["train_router"] is cfg["train_router"] is False
    assert set(cfg["assumed"]) >= {"qk_norm", "indexer", "chunks", "weights"}
    assert len(cfg["departures"]) >= 4


def test_flops_against_a_count_by_hand():
    cfg = harness.Cell(CELL).config
    # W_q and W_o 2048 x 32 x 128, W_k and W_v 2048 x 4 x 128
    attn = 2 * 8_388_608 + 2 * 1_048_576
    assert flops.attention_params(cfg) == attn == 18_874_368
    # W_qI 2048 x 16 x 64, W_kI 2048 x 64, W_w 2048 x 16
    assert flops.indexer_params(cfg) == 2_097_152 + 131_072 + 32_768
    assert flops.expert_params(cfg) == 3 * 2048 * 768 == 4_718_592
    assert flops.held_share(cfg) == 0.125
    # what the program's init makes, to the parameter
    spec = train.build_spec(cfg)
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    assert flops.total_params(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(shapes)) == 465_391_104
    assert cfg["parameters"]["attention_a_layer"] == attn + 256
    assert cfg["parameters"]["indexer_a_layer"] \
        == flops.indexer_params(cfg) + 128
    # a layer: attention, the router's 128 outputs, 8 x 1/8 of an expert
    matmul = 4 * (attn + 262_144 + 4_718_592) + 18992 * 2048
    assert flops.matmul_params_per_token(cfg) == pytest.approx(matmul)
    # sum_t min(t + 1, 2048) over 16,384 rows; the causal triangle
    assert flops.selected_pairs(16384, 2048) == 31_458_304
    assert flops.causal_pairs(16384) == 134_225_920
    assert flops.scored_pairs(16384, 2048) == 134_225_920 - 2_098_176
    assert flops.selected_pairs(1000, 2048) == flops.causal_pairs(1000)
    assert flops.scored_pairs(1000, 2048) == 0
    # attention: 4 layers x 3 x 4 x 128 x 32 heads x 1,920.06 keys a row
    attention = 4 * 3 * 4 * 128 * 32 * 31_458_304 / 16384
    assert flops.attention_flops_per_token(cfg, 16384) \
        == pytest.approx(attention)
    # the indexer, forward: 2 FLOPs a parameter; 16 heads x (64 + 1)
    # multiply-adds a scored pair, 8,064.4 scored pairs a row
    index = 4 * (2 * 2_260_992 + 2 * 16 * 65 * 132_127_744 / 16384)
    assert flops.index_flops_per_token(cfg, 16384) == pytest.approx(index)
    per_token = flops.train_flops_per_token(cfg, 16384)
    assert per_token == pytest.approx(6 * matmul + attention + index)
    assert per_token / 1e9 == pytest.approx(1.2686, abs=1e-3)
    assert flops.routed_flops_per_token(cfg) / per_token == pytest.approx(
        0.0893, abs=1e-3)


def test_sparse_attention_call_counts_the_selected_pairs():
    cfg = harness.Cell(CELL).config
    pairs = 32 * 31_458_304
    rows = 16384 * 128 * 4
    bits = 134_225_920 / 8
    f, b = flops.sparse_attention_call(1, cfg, 16384, 4, backward=False)
    assert f == 4 * 128 * pairs and b == rows * (64 + 8) + bits
    f, b = flops.sparse_attention_call(1, cfg, 16384, 4, backward=True)
    assert f == 10 * 128 * pairs and b == rows * (128 + 16) + bits
    # the chip's FLOPs bind, not its bytes; 36.6 ms a layer in all
    assert f / 197e12 > b / 819e9
    least = sum(flops.sparse_attention_call(1, cfg, 16384, 4, backward=x)[0]
                for x in (False, True)) / 197e12
    assert least * 1e3 == pytest.approx(9.157, abs=1e-2)


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


def _another_selection(monkeypatch, topk):
    """The program selecting ``topk`` keys a row where the configuration
    (and so the reference) says 32."""
    build = train.build_spec

    def other(config):
        config = json.loads(json.dumps(config))
        config["program"]["kwargs"]["topk"] = topk
        return build(config)

    monkeypatch.setattr(train, "build_spec", other)


@pytest.mark.parametrize("broken,failing", [
    ("frozen", "param_change_norm_gap_worst_leaf"),
    ("rows_left_out", "loss_gap_max"),
    (96, "first_grad_sample_rel_err"),     # attends to ALL earlier keys
    (16, "first_grad_sample_rel_err"),     # selects half as many
])
def test_broken_step_is_not_correct(monkeypatch, capsys, broken, failing):
    if isinstance(broken, int):
        _another_selection(monkeypatch, broken)
    else:
        from benchmark import control

        build = train.build_session
        monkeypatch.setattr(
            train, "build_session",
            lambda *a, **k: control.BROKEN[broken](build(*a, **k)))
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
        want = ref.train_steps(p0, check, row_block=2, sample_seed=seed)
        ctl = ref.train_steps(p0, check, row_block=2, compute=compute,
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


def test_the_selection_reaches_the_kernel_as_words_not_bytes():
    """``narrow_product_operands`` counts every operand of a Pallas call
    narrower than float32: the selection goes in as int32 words, and the
    step of the program with its kernel, lowered for the TPU, counts 0."""
    import functools

    flash = importlib.import_module("autodist_tpu.ops.flash_attention")
    model = importlib.import_module("autodist_tpu.models.gqa_dsa_moe_lm")
    # the tiny widths at a length the TPU's tiles divide (nothing runs)
    kwargs = dict(tiny_cell().config["program"]["kwargs"], dtype=jnp.float32,
                  seq_len=512, topk=256, block_k=256, index_rows=256,
                  moe_slice=512)
    spec = model.gqa_dsa_moe_lm(**kwargs, attn_fn=functools.partial(
        flash.flash_attention, interpret=False, block_k=256))
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    text = jax.jit(jax.grad(spec.loss_fn)).trace(
        shapes, {"tokens": jax.ShapeDtypeStruct((1, 512), jnp.int32)},
    ).lower(lowering_platforms=("tpu",)).as_text()
    calls = [ln for ln in text.splitlines() if "@tpu_custom_call" in ln]
    assert len(calls) == 4 and all("x16x512xi32>" in ln for ln in calls)
    assert train.narrow_product_operands(text, "float32") == 0
    # handed as bytes, the same selection would be counted
    assert train.narrow_product_operands(
        text.replace("x16x512xi32>", "x16x512xi8>"), "float32") == 4


def test_the_reference_keeps_to_plain_jax():
    """No kernel, no grouped product, no packed words, no threshold
    search, nothing of the program."""
    with open(os.path.join(os.path.dirname(HERE), "reference",
                           "keye_vl2.py")) as f:
        code = f.read().split('"""', 2)[2]
    for word in ("autodist_tpu", "ragged_dot", "pallas", "argsort",
                 "jnp.sort", "lax.sort", "fori_loop", "bitcast", ">>"):
        assert word not in code, word
    assert "jax.lax.top_k(" in code


# ---------------------------------------------------------------------------
# the new readers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_readers_return_none_on_a_gpt2_run(metric):
    """An untraced run of a GPT-2 cell against a program registry that
    holds no gauges of the sparse attention: every new reader returns None
    and raises nothing (the parent commit's side of a traced run)."""
    from autodist_tpu.telemetry import registry

    registry.reset_for_testing()
    cell = harness.Cell("train.gpt2-tiny.cpu",
                        root=os.path.join(HERE, "data"))
    run = harness.Run(cell, 1, 1.0, True, jax.devices()[:1],
                      time.perf_counter())
    run.counters.update(steps=3, tokens_per_step=256, step_s=[0.1] * 3,
                        seq_len=64, global_batch=4)
    run.peaks = PEAKS
    assert reader(metric)(run) is None


def traced_run(monkeypatch):
    run = harness.Run(harness.Cell(CELL), 1, 1.0, True, jax.devices()[:1],
                      time.perf_counter())
    run.peaks = PEAKS
    run.trace_reduction = xplane.reduce(CUT, 1)
    monkeypatch.setattr(run.tracer, "xplane_path", lambda: CUT)
    run.counters.update(global_batch=1, seq_len=16384)
    return run


def test_mfu_and_pairs_from_counters(monkeypatch):
    from autodist_tpu.telemetry import registry

    run = traced_run(monkeypatch)
    run.counters.update(steps=20, tokens_per_step=16384, step_s=[1.0] * 20)
    per_token = flops.train_flops_per_token(run.cell.config, 16384)
    assert reader("dsa_moe_train_mfu_pct")(run) == pytest.approx(
        100 * 16384 * per_token / 197e12)
    registry.reset_for_testing()
    assert reader("dsa_pairs_computed_per_selected_pair")(run) is None
    for kind, pairs in (("computed", 4 * 528 * 512 * 512),
                        ("selected", 4 * 31_458_304)):
        registry.gauge("autodist_dsa_pairs_per_step", "", {"kind": kind}
                       ).set(pairs)
    assert reader("dsa_pairs_computed_per_selected_pair")(run) \
        == pytest.approx(4.4, abs=0.01)
    registry.reset_for_testing()


def test_readers_on_the_recorded_step(monkeypatch, capsys):
    """A cut of the cell's traced run on the v5e (one whole step,
    operations of 100 us or more and every kernel, each with its
    ``tf_op``): eight ``sparse_attn`` calls a step (a forward and a fused
    backward for each of 4 layers, none run twice), bound by FLOPs both
    ways; the indexer and the selection by their scopes; the routed
    layer's own scopes read as in the kanana cell."""
    run = traced_run(monkeypatch)
    steps, calls, seconds = run.trace_reduction.ops_in_module_runs(
        r"^jit_step\b", r'^%?sparse_attn[\w.\-]* = .*tpu_custom_call')
    assert (steps, calls) == (1, 8)
    share = reader("sparse_attention_roofline")(run)
    assert share == pytest.approx(100 * 36.628e-3 / seconds, rel=1e-3)
    assert 5.0 < share < 100.0
    select = reader("dsa_index_select_device_pct")(run)
    assert 2.0 < select < 40.0
    routed = reader("moe_routed_device_pct")(run)
    assert 10.0 < routed < 60.0
    out = capsys.readouterr().out
    assert "bound by flops forward and flops backward" in out
    for scope in ("dsa/index", "dsa/select", "moe/route", "moe/experts",
                  "moe/combine"):
        assert scope in out
