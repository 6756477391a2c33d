"""What the SmallThinker share brings to the benchmark: its cell's files,
``flops_swa_moe.py`` against a count by hand, the new reference deciding
``correct`` at test size (a sound run, the timed path broken underneath,
each of the four wrong programs in the stated model's place, the
controls), and the new readers against a run that has nothing for them
and against gauges and counters set by hand."""
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

import benchmark.run as brun
from benchmark import flops_swa_moe as flops
from benchmark import harness
from benchmark.entries import train

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train.smallthinker-21b-a3b.ep8-share.seq16384"
DATA = os.path.join(HERE, "data_smallthinker")
TINY = "train.smallthinker-tiny.cpu"
PEAKS = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
NEW_READERS = ("swa_moe_train_mfu_pct", "window_attention_roofline",
               "swa_pairs_computed_per_attended_pair",
               "global_attention_roofline")
FOUR = ("train.gpt2-medium.1chip", "train.gpt2-medium.dp4",
        "train.kanana-2-30b-a3b.ep8-share.seq4096",
        "train.keye-vl-2.0-30b-a3b.ep8-share.seq16384")


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
    assert cell.config["reference"] == "smallthinker_21b_a3b_ep8_share"
    assert callable(train.reference_module(cell.config).train_steps)
    # the traffic file the keye cell uses, untouched
    assert cell.entry_row["traffic"] == "pretrain-seq16384-b1"
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
                "dsa_moe_train_mfu_pct", "sparse_attention_roofline",
                "dsa_index_select_device_pct", "collective_exposed_pct",
                "dsa_pairs_computed_per_selected_pair"} & rows
    assert {m["name"] for m in cell.metric_rows("end_to_end")} == {
        "train_tokens_s_chip", "setup_s"}
    for other in FOUR:
        assert not set(NEW_READERS) & {m["name"] for m in harness.Cell(
            other).metric_rows("per_layer")}


def test_nothing_the_benchmark_had_moved_but_the_lists_that_take_the_cell():
    """``BENCHMARK.json``: one configuration, one cell and three rows at
    the END of their lists; every entry that was there is as it was but
    for the new cell's name at the end of its ``workloads``."""
    bench = harness.Cell(CELL).bench
    assert bench["configs"][-1]["name"] == "smallthinker-21b-a3b.ep8-share"
    assert [w["name"] for w in bench["workloads"]] == list(FOUR) + [CELL]
    new = bench["per_layer"][-len(NEW_READERS):]
    assert [m["name"] for m in new] == list(NEW_READERS)
    assert all(m["workloads"] == [CELL] for m in new)
    assert bench["run_seconds"] == 20
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    listed = [m for m in bench["end_to_end"] + bench["per_layer"][:-3]
              if CELL in m.get("workloads", ())]
    assert all(m["workloads"][-1] == CELL for m in listed)
    assert {m["name"] for m in listed} >= {
        "train_tokens_s_chip", "moe_routed_device_pct",
        "moe_rows_computed_per_routed_row", "hbm_peak_gb.train"}
    for row in bench["configs"][-1:] + bench["workloads"][-1:]:
        assert len(row["why"]) <= 200


def test_every_width_is_the_sources_and_the_cut_is_stated():
    cfg = harness.Cell(CELL).config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == cfg["source"])
        assert row["name"] == "SmallThinker-21BA3B-Instruct"
        differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differ == set(cfg["reduced"])
        # the layouts are cut to their first period, not rewritten
        for key in ("sliding_window_layout", "rope_layout"):
            assert cfg[key] == row["config"][key][:4] == [0, 1, 1, 1]
    assert cfg["reduced"] == ["num_hidden_layers", "moe_num_primary_experts",
                              "vocab_size", "sliding_window_layout",
                              "rope_layout"]
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == 8
    assert dep["experts_held"] == [0, cfg["moe_num_primary_experts"]] \
        == [0, 8]
    assert dep["num_experts_published"] == 64
    assert dep["num_hidden_layers_published"] == 52
    assert cfg["vocab_size"] * 8 == dep["vocab_size_published"] == 151936
    kw = cfg["program"]["kwargs"]
    assert (kw["d_model"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"], kw["window"], kw["d_expert"], kw["num_experts"],
            kw["top_k"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["sliding_window_size"], cfg["moe_ffn_hidden_size"], 64,
        cfg["moe_num_active_primary_experts"]) == (
        2560, 28, 4, 128, 4096, 768, 64, 6)
    assert (kw["rope_theta"], kw["rms_eps"]) == (
        cfg["rope_theta"], cfg["rms_norm_eps"])
    assert kw["window_layout"] == cfg["sliding_window_layout"]
    assert kw["rope_layout"] == cfg["rope_layout"]
    assert kw["vocab_size"] == cfg["vocab_size"]
    assert kw["num_layers"] == cfg["num_hidden_layers"] == 4
    assert kw["seq_len"] == cfg["max_position_embeddings"] == 16384
    assert kw["train_router"] is cfg["train_router"] is False
    # the reference reads the top-level key, the program its kwargs
    assert kw["embed_scale"] == cfg["embed_scale"] == 10000.0
    assert set(cfg["assumed"]) >= {"router_input", "gate", "window",
                                   "layouts", "experts", "weights"}
    assert len(cfg["departures"]) >= 5


def test_flops_against_a_count_by_hand():
    cfg = harness.Cell(CELL).config
    # W_q and W_o 2560 x 28 x 128, W_k and W_v 2560 x 4 x 128
    attn = 2 * 9_175_040 + 2 * 1_310_720
    assert flops.attention_params(cfg) == attn == 20_971_520
    assert flops.expert_params(cfg) == 3 * 2560 * 768 == 5_898_240
    assert flops.held_share(cfg) == 0.125
    # what the program's init makes, to the parameter
    spec = train.build_spec(cfg)
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    assert flops.total_params(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(shapes)) == 370_547_200
    p = cfg["parameters"]
    assert p["attention_a_layer"] == attn
    assert p["router_a_layer"] == 2560 * 64
    assert p["held_experts_a_layer"] == 8 * 5_898_240
    assert 4 * (attn + p["router_a_layer"] + p["norms_a_layer"]
                + p["held_experts_a_layer"]) + p["embedding_and_head"] \
        + p["final_norm"] == 370_547_200
    # a layer: attention, the router's 64 outputs, 6 x 1/8 of an expert
    matmul = 4 * (attn + 163_840 + 0.75 * 5_898_240) + 18992 * 2560
    assert flops.matmul_params_per_token(cfg) == pytest.approx(matmul)
    # sum_t min(t + 1, 4096) over 16,384 rows; the causal triangle
    assert flops.window_pairs(16384, 4096) == 58_722_304
    assert flops.causal_pairs(16384) == 134_225_920
    assert flops.window_pairs(1000, 4096) == flops.causal_pairs(1000)
    assert flops.window_layers(cfg) == 3
    assert flops.attended_pairs(cfg, 16384) == 310_392_832
    # attention: 3 x 4 x 128 x 28 heads x 18,944.9 keys a row over a period
    attention = 3 * 4 * 128 * 28 * 310_392_832 / 16384
    assert flops.attention_flops_per_token(cfg, 16384) \
        == pytest.approx(attention)
    per_token = flops.train_flops_per_token(cfg, 16384)
    assert per_token == pytest.approx(6 * matmul + attention)
    assert per_token / 1e9 == pytest.approx(1.7199, abs=1e-3)
    assert attention / per_token == pytest.approx(0.4737, abs=1e-3)
    assert flops.routed_flops_per_token(cfg) / per_token == pytest.approx(
        0.0617, abs=1e-3)


def test_window_attention_call_counts_the_windows_pairs():
    cfg = harness.Cell(CELL).config
    pairs = 28 * 58_722_304
    rows = 16384 * 128 * 4
    f, b = flops.window_attention_call(1, cfg, 16384, 4, backward=False)
    assert f == 4 * 128 * pairs and b == rows * (56 + 8)
    f, b = flops.window_attention_call(1, cfg, 16384, 4, backward=True)
    assert f == 10 * 128 * pairs and b == rows * (112 + 16)
    # the chip's FLOPs bind, not its bytes; 14.96 ms a window layer in all
    assert f / 197e12 > b / 819e9
    least = sum(flops.window_attention_call(1, cfg, 16384, 4, backward=x)[0]
                for x in (False, True)) / 197e12
    assert least * 1e3 == pytest.approx(14.957, abs=1e-2)


def test_global_attention_call_counts_the_causal_triangle():
    cfg = harness.Cell(CELL).config
    pairs = 28 * 134_225_920
    rows = 16384 * 128 * 4
    f, b = flops.global_attention_call(1, cfg, 16384, 4, backward=False)
    assert f == 4 * 128 * pairs and b == rows * (56 + 8)
    f, b = flops.global_attention_call(1, cfg, 16384, 4, backward=True)
    assert f == 10 * 128 * pairs and b == rows * (112 + 16)
    assert f / 197e12 > b / 819e9
    least = sum(flops.global_attention_call(1, cfg, 16384, 4, backward=x)[0]
                for x in (False, True)) / 197e12
    assert least * 1e3 == pytest.approx(34.189, abs=1e-2)


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


#: the four wrong programs of issue 36 at test size -> the program's kwargs
WRONG = {"all_keys": {"window": 96},
         "half_the_window": {"window": 20},
         "rotary_in_global_layers": {"rope_layout": [1, 1, 1, 1]},
         "router_reads_the_stream": {"router_before_attention": False}}


def _another_program(monkeypatch, kwargs):
    """The program computing another model where the configuration (and so
    the reference) states this one."""
    build = train.build_spec

    def other(config):
        config = json.loads(json.dumps(config))
        config["program"]["kwargs"].update(kwargs)
        return build(config)

    monkeypatch.setattr(train, "build_spec", other)


@pytest.mark.parametrize("broken,failing", [
    ("frozen", "param_change_norm_gap_worst_leaf"),
    ("rows_left_out", "loss_gap_max"),
    ("all_keys", "first_grad_sample_rel_err"),
    ("half_the_window", "first_grad_sample_rel_err"),
    # the pooled sample is mostly the tables' gradient once the rows enter
    # scaled (``embed_scale``); one layer's rotary shows in its own leaves
    ("rotary_in_global_layers", "first_grad_norm_gap_worst_leaf"),
    ("router_reads_the_stream", "first_grad_sample_rel_err"),
])
def test_broken_step_is_not_correct(monkeypatch, capsys, broken, failing):
    if broken in WRONG:
        _another_program(monkeypatch, WRONG[broken])
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


def test_the_step_with_its_kernels_lowers_to_float32_operands():
    """The step of the program with its kernels, lowered for the TPU: two
    Pallas calls a layer (a forward and a fused backward), the window
    layers' and the global layers' apart by name, every operand float32
    (``narrow_product_operands`` counts 0)."""
    import functools
    import importlib

    flash = importlib.import_module("autodist_tpu.ops.flash_attention")
    model = importlib.import_module("autodist_tpu.models.swa_moe_lm")
    # the tiny widths at a length the TPU's tiles divide (nothing runs)
    kwargs = dict(tiny_cell().config["program"]["kwargs"], dtype=jnp.float32,
                  seq_len=512, window=200, block_k=128, moe_slice=512)
    spec = model.swa_moe_lm(**kwargs, attn_fn=functools.partial(
        flash.flash_attention, interpret=False, block_q=128, block_k=128))
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    lowered = jax.jit(jax.grad(spec.loss_fn)).trace(
        shapes, {"tokens": jax.ShapeDtypeStruct((1, 512), jnp.int32)},
    ).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    calls = [ln for ln in text.splitlines() if "@tpu_custom_call" in ln]
    assert len(calls) == 8
    assert train.narrow_product_operands(text, "float32") == 0
    # the scopes the readers find the kernels by, in the locations
    named = lowered.as_text(debug_info=True)
    assert "swa/attention/window_attn" in named
    assert "swa/attention/global_attn" in named


def test_the_reference_keeps_to_plain_jax():
    """No kernel, no grouped product, no sort, no budget, nothing of the
    program; the window is a mask from positions."""
    with open(os.path.join(os.path.dirname(HERE), "reference",
                           "smallthinker.py")) as f:
        code = f.read().split('"""', 2)[2]
    for word in ("autodist_tpu", "ragged_dot", "pallas", "argsort",
                 "jnp.sort", "lax.sort", "fori_loop", "top_k(", "switch",
                 "row_budgets", "flash"):
        assert word not in code, word
    assert "pos[:, None] - s.window" in code
    assert "jax.nn.relu(gate)" in code


# ---------------------------------------------------------------------------
# the new readers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_readers_return_none_on_a_gpt2_run(metric):
    """A run of a GPT-2 cell against a program registry that holds no
    gauges of the window attention: every new reader returns None and
    raises nothing (the parent commit's side of a traced run)."""
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


def a_run():
    run = harness.Run(harness.Cell(CELL), 1, 1.0, True, jax.devices()[:1],
                      time.perf_counter())
    run.peaks = PEAKS
    run.counters.update(global_batch=1, seq_len=16384)
    return run


def test_mfu_and_pairs_from_counters():
    from autodist_tpu.telemetry import registry

    run = a_run()
    run.counters.update(steps=20, tokens_per_step=16384, step_s=[1.0] * 20)
    per_token = flops.train_flops_per_token(run.cell.config, 16384)
    assert reader("swa_moe_train_mfu_pct")(run) == pytest.approx(
        100 * 16384 * per_token / 197e12)
    registry.reset_for_testing()
    assert reader("swa_pairs_computed_per_attended_pair")(run) is None
    for kind, pairs in (("computed", 28 * (528 + 3 * 252) * 512 * 512),
                        ("attended", 28 * 310_392_832)):
        registry.gauge("autodist_swa_pairs_per_step", "", {"kind": kind}
                       ).set(pairs)
    assert reader("swa_pairs_computed_per_attended_pair")(run) \
        == pytest.approx(1.0844, abs=1e-4)
    registry.reset_for_testing()


class _Reduction:
    """What ``xplane.Reduction.ops_in_module_runs`` gives for the window
    kernels: 6 calls a step (a forward and a fused backward for each of
    three window layers), ``seconds`` in all."""

    def __init__(self, steps, calls, seconds):
        self.found = steps, calls, seconds
        self.asked = None

    def ops_in_module_runs(self, module, op):
        self.asked = module, op
        return self.found


def test_window_roofline_from_the_kernels_seconds(capsys):
    import re

    run = a_run()
    assert reader("window_attention_roofline")(run) is None     # no trace
    run.trace_reduction = _Reduction(5, 30, 5 * 0.080)
    share = reader("window_attention_roofline")(run)
    # three window layers x 14.957 ms at the peak over 80 ms a step
    assert share == pytest.approx(100 * 3 * 14.957e-3 / 0.080, rel=1e-3)
    module, op = run.trace_reduction.asked
    assert re.search(op, '%window_attn.12 = (f32[1,28,16384,128]{3,2,1,0}) '
                     'custom-call(), custom_call_target="tpu_custom_call"')
    assert not re.search(op, '%global_attn.4 = (f32[1,28,16384,128]) '
                         'custom-call(), custom_call_target='
                         '"tpu_custom_call"')
    assert re.search(module, "jit_step(123)")
    out = capsys.readouterr().out
    assert "2 a window layer" in out
    assert "bound by flops forward and flops backward" in out
    run.trace_reduction = _Reduction(5, 0, 0.0)     # a program without it
    assert reader("window_attention_roofline")(run) is None


def test_global_roofline_from_the_kernels_seconds(capsys):
    import re

    run = a_run()
    assert reader("global_attention_roofline")(run) is None     # no trace
    run.trace_reduction = _Reduction(5, 10, 5 * 0.048)
    share = reader("global_attention_roofline")(run)
    # one global layer x 34.189 ms at the peak over 48 ms a step
    assert share == pytest.approx(100 * 34.189e-3 / 0.048, rel=1e-3)
    module, op = run.trace_reduction.asked
    assert re.search(op, '%global_attn.4 = (f32[1,28,16384,128]{3,2,1,0}) '
                     'custom-call(), custom_call_target="tpu_custom_call"')
    assert not re.search(op, '%window_attn.12 = (f32[1,28,16384,128]) '
                         'custom-call(), custom_call_target='
                         '"tpu_custom_call"')
    assert re.search(module, "jit_step(123)")
    out = capsys.readouterr().out
    assert "2 a global layer" in out
    assert "bound by flops forward and flops backward" in out
    run.trace_reduction = _Reduction(5, 0, 0.0)     # a program without it
    assert reader("global_attention_roofline")(run) is None
