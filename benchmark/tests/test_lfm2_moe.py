"""What the LFM2-8B-A1B share brings to the benchmark: its cell's files,
``flops_sconv_moe.py`` against a count by hand, the new reference deciding
``correct`` at test size (a sound run, the timed path broken underneath,
four other models in the stated one's place, the controls), and the new
readers against a run that has nothing for them and against counters and
traces set by hand."""
import functools
import json
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

import benchmark.run as brun
from benchmark import flops_sconv_moe as flops
from benchmark import harness
from benchmark.entries import train

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train.lfm2-8b-a1b.ep4-share.seq8192"
DATA = os.path.join(HERE, "data_lfm2_moe")
TINY = "train.lfm2-moe-tiny.cpu"
PEAKS = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
NEW_READERS = ("sconv_moe_train_mfu_pct", "short_conv_mixer_roofline",
               "sconv_conv_device_pct", "gqa_attention_roofline")
SIX = ("train.gpt2-medium.1chip", "train.gpt2-medium.dp4",
       "train.kanana-2-30b-a3b.ep8-share.seq4096",
       "train.keye-vl-2.0-30b-a3b.ep8-share.seq16384",
       "train.smallthinker-21b-a3b.ep8-share.seq16384",
       "train.qwen3-next-80b-a3b.ep16-share.seq8192")


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
    assert cell.chips == 1
    assert cell.config["reference"] == "lfm2_8b_a1b_ep4_share"
    assert callable(train.reference_module(cell.config).train_steps)
    assert cell.traffic == {**cell.traffic, "kind": "lm_batches",
                            "seq_len": 8192, "global_batch": 4}
    assert set(cell.workload["limits"]) == {
        "loss_gap_max", "first_grad_norm_gap_worst_leaf",
        "param_change_norm_gap_worst_leaf", "first_grad_sample_rel_err"}
    rows = {m["name"] for m in cell.metric_rows("per_layer")}
    assert set(NEW_READERS) <= rows
    assert {"compile_s", "train_step_ms_p50", "device_idle_pct.train",
            "hbm_peak_gb.train", "moe_routed_device_pct",
            "moe_rows_computed_per_routed_row", "step_unscoped_device_pct",
            "step_projection_device_pct"} <= rows
    assert len(rows) == 21 + len(NEW_READERS)
    assert not {"train_mfu_pct", "flash_attention_roofline",
                "mla_attention_roofline", "gdn_moe_train_mfu_pct",
                "gated_attention_roofline", "gdn_scan_roofline",
                "collective_exposed_pct"} & rows
    assert {m["name"] for m in cell.metric_rows("end_to_end")} == {
        "train_tokens_s_chip", "setup_s"}
    for other in SIX:
        assert not set(NEW_READERS) & {m["name"] for m in harness.Cell(
            other).metric_rows("per_layer")}


def test_nothing_the_benchmark_had_moved_but_the_lists_that_take_the_cell():
    """``BENCHMARK.json``: one configuration, one cell and four rows at
    the END of their lists; every entry that was there is as it was but
    for the new cell's name at the end of its ``workloads``."""
    bench = harness.Cell(CELL).bench
    assert bench["configs"][-1]["name"] == "lfm2-8b-a1b.ep4-share"
    assert [w["name"] for w in bench["workloads"]] == list(SIX) + [CELL]
    new = bench["per_layer"][-len(NEW_READERS):]
    assert [m["name"] for m in new] == list(NEW_READERS)
    assert all(m["workloads"] == [CELL] for m in new)
    assert all(m["moves"] == "train_tokens_s_chip" for m in new)
    assert bench["run_seconds"] == 20
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    listed = [m for m in bench["end_to_end"] + bench["per_layer"][:-4]
              if CELL in m.get("workloads", ())]
    assert len(listed) == 22
    assert all(m["workloads"][-1] == CELL and m["workloads"][-2] == SIX[-1]
               for m in listed)
    for row in bench["configs"][-1:] + bench["workloads"][-1:]:
        assert len(row["why"]) <= 200


def test_every_width_is_the_sources_and_the_cut_is_stated():
    cfg = harness.Cell(CELL).config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == cfg["source"])
        assert row["name"] == "LFM2-8B-A1B"
        differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differ == set(cfg["reduced"])
        assert cfg["deployment"]["layer_types_published"] \
            == row["config"]["layer_types"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types", "num_experts", "vocab_size"]
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == 4
    assert dep["experts_held"] == [0, cfg["num_experts"]] == [0, 8]
    assert dep["num_experts_published"] == 32
    assert dep["num_hidden_layers_published"] == 24
    assert dep["num_dense_layers_published"] == 2
    assert cfg["vocab_size"] * 4 == dep["vocab_size_published"] == 65536
    # layer 0 and layers 2-5 of the published list: a dense conv layer and
    # one whole period of what follows the dense layers
    published = dep["layer_types_published"]
    assert cfg["layer_types"] == [published[0]] + published[2:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 5
    kw = cfg["program"]["kwargs"]
    assert (kw["d_model"], kw["conv_kernel"], kw["num_heads"],
            kw["num_kv_heads"], kw["head_dim"], kw["d_ff"], kw["d_expert"],
            kw["num_experts"], kw["top_k"], kw["routed_scale"]) == (
        cfg["hidden_size"], cfg["conv_L_cache"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"],
        cfg["hidden_size"] // cfg["num_attention_heads"],
        cfg["intermediate_size"], cfg["moe_intermediate_size"], 32,
        cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]) == (
        2048, 3, 32, 8, 64, 7168, 1792, 32, 4, 1)
    assert (kw["rope_theta"], kw["rms_eps"]) == (cfg["rope_theta"],
                                                 cfg["norm_eps"])
    assert kw["layer_types"] == cfg["layer_types"]
    assert kw["num_dense_layers"] == cfg["num_dense_layers"] == 1
    assert kw["vocab_size"] == cfg["vocab_size"]
    assert kw["experts_held"] == dep["experts_held"]
    assert kw["seq_len"] == 8192
    # the routed layer's chunk is its own rule's: one slice's picks
    assert "moe_chunk" not in kw and kw["moe_slice"] * kw["top_k"] == 16384
    assert kw["train_router"] is cfg["train_router"] is False
    assert kw["tie_embedding"] is cfg["tie_embedding"]
    assert kw.get("embed_scale", 1.0) == cfg.get("embed_scale", 1.0)
    assert set(cfg["assumed"]) >= {"conv_mixer", "attention_mixer", "norms",
                                   "experts", "weights", "sequence"}
    # rung C of issue 43: the scale and the untied head, both departures
    assert (cfg["embed_scale"], cfg["tie_embedding"]) == (10000.0, False)
    assert len(cfg["departures"]) >= 6
    for word in ("gates", "float32", "highest"):
        assert word in cfg["precision"]["stated"]


def test_flops_against_a_count_by_hand():
    cfg = harness.Cell(CELL).config
    # W_in 2048 x 6144, the taps 2048 x 3, W_out 2048 x 2048
    conv = 12_582_912 + 6_144 + 4_194_304
    assert flops.conv_mixer_params(cfg) == conv == 16_783_360
    # W_q 2048 x 2048, W_k and W_v 2048 x 512, two norms, W_o 2048 x 2048
    attention = 4_194_304 + 2 * 1_048_576 + 128 + 4_194_304
    assert flops.attention_mixer_params(cfg) == attention == 10_485_888
    assert flops.dense_ffn_params(cfg) == 3 * 2048 * 7168 == 44_040_192
    assert flops.expert_params(cfg) == 3 * 2048 * 1792 == 11_010_048
    assert flops.router_params(cfg) == 65_536 + 32
    assert flops.held_share(cfg) == 1 / 4
    assert (flops.conv_layers(cfg), flops.attention_layers(cfg),
            flops.expert_layers(cfg), flops.head_dim(cfg)) == (4, 1, 4, 64)
    # what the program's init makes, to the parameter
    spec = train.build_spec(cfg)
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    total = cfg["parameters"]["total"] if cfg["tie_embedding"] \
        else 541_374_720
    assert flops.total_params(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(shapes)) == total
    p = cfg["parameters"]
    expert_layer = (p["held_experts_a_layer"] + p["router_and_bias_a_layer"]
                    + p["norms_a_layer"])
    assert p["conv_mixer_a_layer"] + p["dense_ffn"] + p["norms_a_layer"] \
        == 60_827_648
    assert p["attention_mixer_a_layer"] + expert_layer == 98_635_936
    assert p["conv_mixer_a_layer"] + expert_layer == 104_933_408
    assert (60_827_648 + 98_635_936 + 3 * 104_933_408
            + p["embedding_and_head"] + p["final_norm"]) == p["total"] \
        == 541_374_720
    # 4 x 1/4 of an expert a token and expert layer; the router's 32 outputs
    matmul = (4 * conv + (attention - 128) + 44_040_192
              + 4 * (65_536 + 11_010_048) + 16384 * 2048)
    assert flops.matmul_params_per_token(cfg) == pytest.approx(matmul)
    assert flops.causal_pairs(8192) == 33_558_528
    pairs = 3 * 4 * 64 * 32 * 33_558_528 / 8192
    assert flops.attention_flops_per_token(cfg, 8192) \
        == pytest.approx(pairs)
    per_token = flops.train_flops_per_token(cfg, 8192)
    assert per_token == pytest.approx(6 * matmul + 3 * 4 * 2 * 2048 + pairs)
    assert per_token / 1e9 == pytest.approx(1.2975, abs=1e-3)
    assert flops.routed_flops_per_token(cfg) / per_token == pytest.approx(
        0.2037, abs=1e-3)
    assert 4 * 6 * conv / per_token == pytest.approx(0.3105, abs=1e-3)
    assert pairs / per_token == pytest.approx(0.0776, abs=1e-3)


def test_the_calls_count_the_models_work():
    cfg = harness.Cell(CELL).config
    tokens = 4 * 8192
    # two products of 3 x 2048^2 and 2048^2, three taps and two gates a
    # channel; in and out one [tokens, 2048] float32 each, the weights once
    f, b = flops.short_conv_mixer_call(4, cfg, 8192, 4, backward=False)
    assert f == tokens * (2 * 4 * 2048 * 2048 + 2 * 3 * 2048 + 2 * 2048)
    weights = 4 * (4 * 2048 * 2048 + 3 * 2048)
    assert b == 2 * tokens * 2048 * 4 + weights
    f2, b2 = flops.short_conv_mixer_call(4, cfg, 8192, 4, backward=True)
    assert f2 == 2 * f and b2 == 3 * tokens * 2048 * 4 + 2 * weights
    # the chip's FLOPs bind the mixer as written: 5.58 + 11.17 ms a layer
    assert f / 197e12 > b / 819e9 and f2 / 197e12 > b2 / 819e9
    assert (f + f2) / 197e12 * 1e3 == pytest.approx(16.754, abs=1e-2)
    pairs = 4 * 32 * 33_558_528
    rows = tokens * 64 * 4
    f, b = flops.gqa_attention_call(4, cfg, 8192, 4, backward=False)
    assert f == 4 * 64 * pairs and b == rows * (64 + 16)
    f2, b2 = flops.gqa_attention_call(4, cfg, 8192, 4, backward=True)
    assert f2 == 10 * 64 * pairs and b2 == rows * (128 + 32)
    assert f / 197e12 > b / 819e9 and f2 / 197e12 > b2 / 819e9
    assert (f + f2) / 197e12 * 1e3 == pytest.approx(19.537, abs=1e-2)


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


#: other models in the stated one's place: the REFERENCE's ``wrong``,
#: through ``benchmark/wrong_models.py`` as on the chip (the program then
#: stands against a model with that part left out)
WRONG = ("no_conv", "no_b_gate", "no_qk_norm", "no_rotary")


@pytest.mark.parametrize("broken,failing", [
    ("frozen", "param_change_norm_gap_worst_leaf"),
    ("rows_left_out", "loss_gap_max"),
    ("no_conv", "first_grad_sample_rel_err"),
    ("no_b_gate", "first_grad_sample_rel_err"),
    ("no_qk_norm", "first_grad_sample_rel_err"),
    ("no_rotary", "first_grad_sample_rel_err"),
])
def test_broken_step_is_not_correct(monkeypatch, capsys, broken, failing):
    if broken in WRONG:
        from benchmark import wrong_models

        row = wrong_models.judged(tiny_cell(), jax.devices()[:1],
                                  2**31 + 7, broken)
        assert row["correct"] is False and failing in row["failed"], row
        # the entry finds the stated model again
        assert train.reference_module(tiny_cell().config).SETTINGS.wrong == ""
    else:
        from benchmark import control

        build = train.build_session
        monkeypatch.setattr(
            train, "build_session",
            lambda *a, **k: control.BROKEN[broken](build(*a, **k)))
        assert drive(tiny_cell())["correct"] is False
    out = capsys.readouterr().out
    bad = [ln for ln in out.splitlines() if "NOT CORRECT" in ln]
    assert any(failing in ln for ln in bad), out


def test_no_configuration_can_name_a_wrong_model():
    from benchmark.reference import lfm2_moe

    cfg = dict(harness.Cell(CELL).config)
    assert lfm2_moe.Settings.from_config(cfg).wrong == ""
    with pytest.raises(ValueError, match="wrong"):
        lfm2_moe.Settings.from_config(dict(cfg, wrong="no_conv"))


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
    for seed in (1, 2):
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
    assert failed == 2


def test_the_step_with_its_kernels_lowers_to_float32_operands():
    """The step of the program with its kernel, lowered for the TPU: a
    forward and a fused backward ``gqa_attn`` for the one attention layer,
    the rows' return to token order, every operand float32
    (``narrow_product_operands`` counts 0), and the scopes the readers
    find the parts by in the locations."""
    import importlib

    flash = importlib.import_module("autodist_tpu.ops.flash_attention")
    model = importlib.import_module("autodist_tpu.models.sconv_moe_lm")
    rows = importlib.import_module("autodist_tpu.ops.rows_to_tokens")
    # the tiny widths at a length the TPU's tiles divide (nothing runs)
    kwargs = dict(tiny_cell().config["program"]["kwargs"], dtype=jnp.float32,
                  seq_len=512, block_k=128, moe_slice=512, head_dim=128)
    spec = model.sconv_moe_lm(
        **kwargs, attn_fn=functools.partial(
            flash.flash_attention, interpret=False, block_q=128,
            block_k=128))
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    was, rows._use_interpret = rows._use_interpret, lambda: False
    try:
        lowered = jax.jit(jax.grad(spec.loss_fn)).trace(
            shapes, {"tokens": jax.ShapeDtypeStruct((1, 512), jnp.int32)},
        ).lower(lowering_platforms=("tpu",))
    finally:
        rows._use_interpret = was
    text = lowered.as_text()
    calls = [ln for ln in text.splitlines() if "@tpu_custom_call" in ln]
    assert len(calls) >= 2
    assert train.narrow_product_operands(text, "float32") == 0
    named = lowered.as_text(debug_info=True)
    for scope in ("gqa/attention/gqa_attn", "sconv/project", "sconv/conv",
                  "ffn/dense", "moe/route"):
        assert scope in named, scope


def test_the_reference_keeps_to_plain_jax():
    """No kernel, no grouped product, no sort, no pad-and-slice
    convolution, nothing of the program; all pairs' scores a block at a
    time and every expert over every token."""
    with open(os.path.join(os.path.dirname(HERE), "reference",
                           "lfm2_moe.py")) as f:
        code = f.read().split('"""', 2)[2]
    for word in ("autodist_tpu", "ragged_dot", "pallas", "argsort",
                 "jnp.sort", "lax.sort", "fori_loop", "top_k(", "switch",
                 "cumsum", "tril", "flash", "conv_general", "causal_conv",
                 "jnp.pad", "lax.slice_in_dim"):
        assert word not in code, word
    assert "moved * taps[:, i]" in code
    assert "picked.sum(-1, keepdims=True)\n" in code and "NORM_EPS" in code
    assert 'ref["head"] if "head" in ref else ref["embed"]' in code


# ---------------------------------------------------------------------------
# the new readers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_readers_return_none_on_a_gpt2_run(metric):
    """A run of a GPT-2 cell: every new reader returns None and raises
    nothing (the parent commit's side of a traced run)."""
    cell = harness.Cell("train.gpt2-tiny.cpu",
                        root=os.path.join(HERE, "data"))
    run = harness.Run(cell, 1, 1.0, True, jax.devices()[:1],
                      time.perf_counter())
    run.counters.update(steps=3, tokens_per_step=256, step_s=[0.1] * 3,
                        seq_len=64, global_batch=4)
    run.peaks = PEAKS
    assert reader(metric)(run) is None


def a_run():
    run = harness.Run(harness.Cell(CELL), 1, 1.0, True, jax.devices()[:1],
                      time.perf_counter())
    run.peaks = PEAKS
    run.counters.update(global_batch=4, seq_len=8192)
    return run


def test_mfu_from_counters():
    run = a_run()
    assert reader("sconv_moe_train_mfu_pct")(run) is None      # no steps
    run.counters.update(steps=20, tokens_per_step=32768, step_s=[1.0] * 20)
    per_token = flops.train_flops_per_token(run.cell.config, 8192)
    assert reader("sconv_moe_train_mfu_pct")(run) == pytest.approx(
        100 * 32768 * per_token / 197e12)


class _Reduction:
    """What ``xplane.Reduction.ops_in_module_runs`` gives for the
    ``gqa_attn`` kernels: 8 calls a step (a forward and a fused backward
    for each of four sequences), ``seconds`` in all."""

    def __init__(self, steps, calls, seconds):
        self.found = steps, calls, seconds
        self.asked = None

    def ops_in_module_runs(self, module, op):
        self.asked = module, op
        return self.found


def test_gqa_attention_roofline_from_the_kernels_seconds(capsys):
    run = a_run()
    assert reader("gqa_attention_roofline")(run) is None       # no trace
    run.trace_reduction = _Reduction(5, 40, 5 * 0.050)
    share = reader("gqa_attention_roofline")(run)
    # one attention layer x 19.537 ms at the peak over 50 ms a step
    assert share == pytest.approx(100 * 19.537e-3 / 0.050, rel=1e-3)
    module, op = run.trace_reduction.asked
    assert re.search(op, '%gqa_attn.4 = (f32[1,32,8192,64]{3,2,1,0}) '
                     'custom-call(), custom_call_target="tpu_custom_call"')
    assert not re.search(op, '%gated_attn.4 = (f32[1,16,8192,256]) '
                         'custom-call(), custom_call_target='
                         '"tpu_custom_call"')
    assert re.search(module, "jit_step(123)")
    assert "bound by flops forward and flops backward" \
        in capsys.readouterr().out
    run.trace_reduction = _Reduction(5, 0, 0.0)     # a program without it
    assert reader("gqa_attention_roofline")(run) is None


def test_mixer_roofline_and_conv_share_read_the_scopes_table(monkeypatch,
                                                            capsys):
    """Both read ``step_scopes.table``: the roofline every operation under
    ``sconv/project`` or ``sconv/conv`` against 4 layers x 16.754 ms, the
    share ``sconv/conv`` alone of all operations."""
    from benchmark import step_scopes

    run = a_run()
    assert reader("short_conv_mixer_roofline")(run) is None    # no trace
    assert reader("sconv_conv_device_pct")(run) is None
    table = step_scopes.Table(steps=5, program_s=5 * 0.7, total_s=5 * 0.69,
                              by_scope={"sconv/project": 5 * 0.110,
                                        "sconv/conv": 5 * 0.040,
                                        "moe/experts": 5 * 0.2})
    monkeypatch.setattr(step_scopes, "table", lambda r: table)
    assert reader("short_conv_mixer_roofline")(run) == pytest.approx(
        100 * 4 * 16.754e-3 / 0.150, rel=1e-3)
    assert "bound by flops forward and flops backward" \
        in capsys.readouterr().out
    assert reader("sconv_conv_device_pct")(run) == pytest.approx(
        100 * 0.040 / 0.69)
    # a program without the layer (the parent): other scopes alone
    table.by_scope = {"moe/experts": 1.0}
    assert reader("short_conv_mixer_roofline")(run) is None
    assert reader("sconv_conv_device_pct")(run) is None
