"""What the Qwen3-Next share brings to the benchmark: its cell's files,
``flops_gdn_moe.py`` against a count by hand, the new reference deciding
``correct`` at test size (a sound run, the timed path broken underneath,
four other models in the stated one's place, the controls), and the new
readers against a run that has nothing for them and against gauges and
counters set by hand."""
import dataclasses
import functools
import json
import os
import re
import time
import types

import jax
import jax.numpy as jnp
import pytest

import benchmark.run as brun
from benchmark import flops_gdn_moe as flops
from benchmark import harness
from benchmark.entries import train

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train.qwen3-next-80b-a3b.ep16-share.seq8192"
DATA = os.path.join(HERE, "data_qwen3_next")
TINY = "train.qwen3-next-tiny.cpu"
PEAKS = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
NEW_READERS = ("gdn_moe_train_mfu_pct", "gdn_scan_roofline",
               "gated_attention_roofline", "gdn_conv_recurrence_device_pct",
               "gdn_flops_computed_per_recurrence_flop")
FIVE = ("train.gpt2-medium.1chip", "train.gpt2-medium.dp4",
        "train.kanana-2-30b-a3b.ep8-share.seq4096",
        "train.keye-vl-2.0-30b-a3b.ep8-share.seq16384",
        "train.smallthinker-21b-a3b.ep8-share.seq16384")


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
    assert cell.config["reference"] == "qwen3_next_80b_a3b_ep16_share"
    assert callable(train.reference_module(cell.config).train_steps)
    assert cell.traffic == {**cell.traffic, "kind": "lm_batches",
                            "seq_len": 8192, "global_batch": 2}
    assert set(cell.workload["limits"]) == {
        "loss_gap_max", "first_grad_norm_gap_worst_leaf",
        "param_change_norm_gap_worst_leaf", "first_grad_sample_rel_err"}
    rows = {m["name"] for m in cell.metric_rows("per_layer")}
    assert set(NEW_READERS) <= rows
    assert {"compile_s", "train_step_ms_p50", "device_idle_pct.train",
            "hbm_peak_gb.train", "moe_routed_device_pct",
            "moe_rows_computed_per_routed_row", "step_unscoped_device_pct",
            "step_projection_device_pct"} <= rows
    assert not {"train_mfu_pct", "flash_attention_roofline",
                "mla_attention_roofline", "mla_moe_train_mfu_pct",
                "dsa_moe_train_mfu_pct", "sparse_attention_roofline",
                "swa_moe_train_mfu_pct", "window_attention_roofline",
                "global_attention_roofline", "collective_exposed_pct"} & rows
    assert {m["name"] for m in cell.metric_rows("end_to_end")} == {
        "train_tokens_s_chip", "setup_s"}
    for other in FIVE:
        assert not set(NEW_READERS) & {m["name"] for m in harness.Cell(
            other).metric_rows("per_layer")}


def test_nothing_the_benchmark_had_moved_but_the_lists_that_take_the_cell():
    """``BENCHMARK.json``: one configuration, one cell and five rows at
    the END of their lists; every entry that was there is as it was but
    for the new cell's name at the end of its ``workloads``."""
    bench = harness.Cell(CELL).bench
    assert bench["configs"][-1]["name"] == "qwen3-next-80b-a3b.ep16-share"
    assert [w["name"] for w in bench["workloads"]] == list(FIVE) + [CELL]
    new = bench["per_layer"][-len(NEW_READERS):]
    assert [m["name"] for m in new] == list(NEW_READERS)
    assert all(m["workloads"] == [CELL] for m in new)
    assert all(m["moves"] == "train_tokens_s_chip" for m in new)
    assert bench["run_seconds"] == 20
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    listed = [m for m in bench["end_to_end"] + bench["per_layer"][:-5]
              if CELL in m.get("workloads", ())]
    assert all(m["workloads"][-1] == CELL
               and m["workloads"][:-1] == list(FIVE)[-len(m["workloads"])
                                                     + 1:]
               for m in listed)
    assert {m["name"] for m in listed} >= {
        "train_tokens_s_chip", "moe_routed_device_pct",
        "moe_rows_computed_per_routed_row", "hbm_peak_gb.train",
        "step_unscoped_device_pct"}
    for row in bench["configs"][-1:] + bench["workloads"][-1:]:
        assert len(row["why"]) <= 200


def test_every_width_is_the_sources_and_the_cut_is_stated():
    cfg = harness.Cell(CELL).config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == cfg["source"])
        assert row["name"] == "Qwen3-Next-80B-A3B-Instruct"
        differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differ == set(cfg["reduced"])
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == 16
    assert dep["experts_held"] == [0, cfg["num_experts"]] == [0, 32]
    assert dep["num_experts_published"] == 512
    assert dep["num_hidden_layers_published"] == 48
    assert cfg["vocab_size"] * 8 == dep["vocab_size_published"] == 151936
    kw = cfg["program"]["kwargs"]
    assert (kw["d_model"], kw["linear_key_heads"], kw["linear_value_heads"],
            kw["linear_head_dim"], kw["conv_kernel"], kw["num_heads"],
            kw["num_kv_heads"], kw["head_dim"], kw["rotary_dim"],
            kw["d_expert"], kw["d_shared"], kw["num_experts"],
            kw["top_k"]) == (
        cfg["hidden_size"], cfg["linear_num_key_heads"],
        cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
        cfg["linear_conv_kernel_dim"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        int(cfg["partial_rotary_factor"] * cfg["head_dim"]),
        cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"],
        512, cfg["num_experts_per_tok"]) == (
        2048, 16, 32, 128, 4, 16, 2, 256, 64, 512, 512, 512, 10)
    assert cfg["linear_value_head_dim"] == cfg["linear_key_head_dim"]
    assert (kw["rope_theta"], kw["rms_eps"], kw["full_interval"]) == (
        cfg["rope_theta"], cfg["rms_norm_eps"],
        cfg["full_attention_interval"])
    assert kw["vocab_size"] == cfg["vocab_size"]
    assert kw["num_layers"] == cfg["num_hidden_layers"] == 4
    assert kw["seq_len"] == 8192
    assert kw["train_router"] is cfg["train_router"] is False
    assert set(cfg["assumed"]) >= {"layouts", "linear_mixer", "full_mixer",
                                   "norms", "experts", "weights"}
    assert len(cfg["departures"]) >= 5
    for word in ("recurrence", "float32", "highest"):
        assert word in cfg["precision"]["stated"]


def test_flops_against_a_count_by_hand():
    cfg = harness.Cell(CELL).config
    # W_qkvz 2048 x 12288, W_ba 2048 x 64, conv 8192 x 4, A_log, dt_bias,
    # the gated norm, W_out 4096 x 2048
    linear = 25_165_824 + 131_072 + 32_768 + 32 + 32 + 128 + 8_388_608
    assert flops.linear_mixer_params(cfg) == linear == 33_718_464
    # W_q 2048 x 8192, W_k and W_v 2048 x 512, two norms, W_o 4096 x 2048
    full = 16_777_216 + 2 * 1_048_576 + 512 + 8_388_608
    assert flops.full_mixer_params(cfg) == full == 27_263_488
    assert flops.expert_params(cfg) == 3 * 2048 * 512 == 3_145_728
    assert flops.shared_params(cfg) == 3_145_728 + 2048
    assert flops.held_share(cfg) == 1 / 16
    assert (flops.linear_layers(cfg), flops.full_layers(cfg)) == (3, 1)
    # what the program's init makes, to the parameter
    spec = train.build_spec(cfg)
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    assert flops.total_params(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(shapes)) == 625_667_136
    p = cfg["parameters"]
    every = (p["router_a_layer"] + p["shared_expert_a_layer"]
             + p["shared_gate_a_layer"] + p["held_experts_a_layer"]
             + p["norms_a_layer"])
    assert p["linear_mixer_a_layer"] + every == 138_582_208
    assert p["full_mixer_a_layer"] + every == 132_127_232
    assert 3 * 138_582_208 + 132_127_232 + p["embedding_and_head"] \
        + p["final_norm"] == 625_667_136
    # a layer: the router's 512 outputs, the shared expert and its gate,
    # 10 x 1/16 of an expert
    every = 1_048_576 + 3_147_776 + 0.625 * 3_145_728
    matmul = (3 * (linear - 192) + (full - 512) + 4 * every
              + 18992 * 2048)
    assert flops.matmul_params_per_token(cfg) == pytest.approx(matmul)
    assert flops.recurrence_flops_per_token(cfg) == 7 * 32 * 128 * 128
    assert flops.causal_pairs(8192) == 33_558_528
    attention = 3 * 4 * 256 * 16 * 33_558_528 / 8192
    assert flops.attention_flops_per_token(cfg, 8192) \
        == pytest.approx(attention)
    per_token = flops.train_flops_per_token(cfg, 8192)
    assert per_token == pytest.approx(
        6 * matmul + 9 * 7 * 32 * 128 * 128 + attention)
    assert per_token / 1e9 == pytest.approx(1.3862, abs=1e-3)
    assert attention / per_token == pytest.approx(0.1452, abs=1e-3)
    assert flops.routed_flops_per_token(cfg) / per_token == pytest.approx(
        0.0340, abs=1e-3)


def test_the_calls_count_the_models_work():
    cfg = harness.Cell(CELL).config
    tokens = 2 * 8192
    # q, k 16 x 128, v and o 32 x 128, g and beta 32 a token, float32
    f, b = flops.gdn_scan_call(2, cfg, 8192, 4, backward=False)
    assert f == tokens * 7 * 32 * 128 * 128
    assert b == tokens * 4 * (2 * 2048 + 4096 + 64 + 4096)
    f2, b2 = flops.gdn_scan_call(2, cfg, 8192, 4, backward=True)
    assert f2 == 2 * f and b2 == tokens * 4 * (2 * (2 * 2048 + 4096 + 64)
                                               + 4096)
    # the chip's BYTES bind the recurrence as written: 0.99 + 1.65 ms
    assert b / 819e9 > f / 197e12 and b2 / 819e9 > f2 / 197e12
    assert (b + b2) / 819e9 * 1e3 == pytest.approx(2.637, abs=1e-2)
    pairs = 2 * 16 * 33_558_528
    rows = tokens * 256 * 4
    f, b = flops.gated_attention_call(2, cfg, 8192, 4, backward=False)
    assert f == 4 * 256 * pairs and b == rows * (32 + 4)
    f2, b2 = flops.gated_attention_call(2, cfg, 8192, 4, backward=True)
    assert f2 == 10 * 256 * pairs and b2 == rows * (64 + 8)
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


#: other models in the stated one's place: the REFERENCE's ``wrong`` (the
#: program then stands against a model with that part left out), or the
#: program's kwargs
WRONG = {"no_decay": "no_decay", "beta_one": "beta_one",
         "no_conv": "no_conv",
         "rotary_over_the_whole_head": {"rotary_dim": 16}}


def _another_model(monkeypatch, wrong):
    if isinstance(wrong, dict):
        build = train.build_spec

        def other(config):
            config = json.loads(json.dumps(config))
            config["program"]["kwargs"].update(wrong)
            return build(config)

        return monkeypatch.setattr(train, "build_spec", other)
    from benchmark.reference import qwen3_next

    bound = vars(train.reference_module(tiny_cell().config))
    s = dataclasses.replace(bound["SETTINGS"], wrong=wrong)
    module = types.SimpleNamespace(**{
        **bound, "SETTINGS": s, **{
            name: functools.partial(getattr(qwen3_next, name), s=s)
            for name in ("train_steps", "lowered_block_grad")}})
    monkeypatch.setattr(train, "reference_module", lambda config: module)


@pytest.mark.parametrize("broken,failing", [
    ("frozen", "param_change_norm_gap_worst_leaf"),
    ("rows_left_out", "loss_gap_max"),
    ("no_decay", "first_grad_sample_rel_err"),
    ("beta_one", "first_grad_sample_rel_err"),
    ("no_conv", "first_grad_sample_rel_err"),
    ("rotary_over_the_whole_head", "first_grad_norm_gap_worst_leaf"),
])
def test_broken_step_is_not_correct(monkeypatch, capsys, broken, failing):
    if broken in WRONG:
        _another_model(monkeypatch, WRONG[broken])
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
    """The step of the program with its kernels, lowered for the TPU: one
    scan kernel a linear layer (its forward; the backward is the plain
    form), a forward and a fused backward ``gated_attn``, every operand
    float32 (``narrow_product_operands`` counts 0: the check knows the new
    kernel as it knows every Pallas call)."""
    import importlib

    flash = importlib.import_module("autodist_tpu.ops.flash_attention")
    gdr = importlib.import_module("autodist_tpu.ops.gated_delta_rule")
    model = importlib.import_module("autodist_tpu.models.gdn_moe_lm")
    # the tiny widths at a length the TPU's tiles divide (nothing runs)
    kwargs = dict(tiny_cell().config["program"]["kwargs"], dtype=jnp.float32,
                  seq_len=512, chunk=64, block_k=128, moe_slice=512,
                  linear_head_dim=128, head_dim=128, rotary_dim=32)
    spec = model.gdn_moe_lm(
        **kwargs, attn_fn=functools.partial(
            flash.flash_attention, interpret=False, block_q=128,
            block_k=128),
        gdn_fn=functools.partial(gdr.gated_delta_rule, chunk=64, segment=4,
                                 interpret=False))
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    lowered = jax.jit(jax.grad(spec.loss_fn)).trace(
        shapes, {"tokens": jax.ShapeDtypeStruct((1, 512), jnp.int32)},
    ).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    calls = [ln for ln in text.splitlines() if "@tpu_custom_call" in ln]
    assert len(calls) == 3 + 2
    assert train.narrow_product_operands(text, "float32") == 0
    # a kernel handed bfloat16 would be counted
    assert train.narrow_product_operands(
        calls[0].replace("xf32>", "xbf16>"), "float32") > 0
    # the scopes the readers find the kernels by, in the locations
    named = lowered.as_text(debug_info=True)
    assert "gdn/recurrence/gdn_scan" in named
    assert "gattn/attention/gated_attn" in named


def test_the_reference_keeps_to_plain_jax():
    """No kernel, no chunk, no triangular solve, no grouped product, no
    sort, nothing of the program; the recurrence is a scan over tokens."""
    with open(os.path.join(os.path.dirname(HERE), "reference",
                           "qwen3_next.py")) as f:
        code = f.read().split('"""', 2)[2]
    for word in ("autodist_tpu", "ragged_dot", "pallas", "argsort",
                 "jnp.sort", "lax.sort", "fori_loop", "top_k(", "switch",
                 "cumsum", "solve", "tril", "flash", "conv_general"):
        assert word not in code, word
    assert "jax.lax.scan(token, s, xs)" in code
    assert "s * jnp.exp(g_t)" in code
    assert "jax.nn.sigmoid(gate)" in code


# ---------------------------------------------------------------------------
# the new readers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_readers_return_none_on_a_gpt2_run(metric):
    """A run of a GPT-2 cell against a program registry that holds no
    gauges of the recurrence: every new reader returns None and raises
    nothing (the parent commit's side of a traced run)."""
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
    run.counters.update(global_batch=2, seq_len=8192)
    return run


def test_mfu_and_flops_ratio_from_counters():
    from autodist_tpu.telemetry import registry

    run = a_run()
    run.counters.update(steps=20, tokens_per_step=16384, step_s=[1.0] * 20)
    per_token = flops.train_flops_per_token(run.cell.config, 8192)
    assert reader("gdn_moe_train_mfu_pct")(run) == pytest.approx(
        100 * 16384 * per_token / 197e12)
    registry.reset_for_testing()
    assert reader("gdn_flops_computed_per_recurrence_flop")(run) is None
    for kind, count in (("computed", 169_984), ("recurrence", 114_688)):
        registry.gauge("autodist_gdn_flops_per_step", "", {"kind": kind}
                       ).set(count * 16384 * 32 * 3)
    assert reader("gdn_flops_computed_per_recurrence_flop")(run) \
        == pytest.approx(1.4821, abs=1e-4)
    registry.reset_for_testing()


class _Reduction:
    """What ``xplane.Reduction.ops_in_module_runs`` gives for the
    ``gated_attn`` kernels: 4 calls a step (a forward and a fused backward
    for each of two sequences), ``seconds`` in all."""

    def __init__(self, steps, calls, seconds):
        self.found = steps, calls, seconds
        self.asked = None

    def ops_in_module_runs(self, module, op):
        self.asked = module, op
        return self.found


def test_gated_attention_roofline_from_the_kernels_seconds(capsys):
    run = a_run()
    assert reader("gated_attention_roofline")(run) is None     # no trace
    run.trace_reduction = _Reduction(5, 20, 5 * 0.030)
    share = reader("gated_attention_roofline")(run)
    # one full layer x 19.537 ms at the peak over 30 ms a step
    assert share == pytest.approx(100 * 19.537e-3 / 0.030, rel=1e-3)
    module, op = run.trace_reduction.asked
    assert re.search(op, '%gated_attn.4 = (f32[1,16,8192,256]{3,2,1,0}) '
                     'custom-call(), custom_call_target="tpu_custom_call"')
    assert not re.search(op, '%gdn_scan.4 = (f32[32,128,64,128]) '
                         'custom-call(), custom_call_target='
                         '"tpu_custom_call"')
    assert re.search(module, "jit_step(123)")
    assert "bound by flops forward and flops backward" \
        in capsys.readouterr().out
    run.trace_reduction = _Reduction(5, 0, 0.0)     # a program without it
    assert reader("gated_attention_roofline")(run) is None


def test_gdn_scan_roofline_sums_what_names_the_scope(monkeypatch, capsys):
    """Every operation inside whole step programs whose ``tf_op`` names
    ``gdn_scan`` (the kernel, the batched products before it, the plain
    scans' bodies of the backward), each once, against the recurrence as
    written: 3 layers x 2.637 ms."""
    run = a_run()
    assert reader("gdn_scan_roofline")(run) is None            # no trace
    ms = 1_000_000
    ops = [("%gdn_scan.1 = f32[32,128,64,128] custom-call()", 0, 10 * ms),
           ("%fusion.7 = f32[16,2,32,64,64] fusion()", 10 * ms, 60 * ms),
           ("%while.3 = (f32[]) while()", 60 * ms, 90 * ms),   # left out
           ("%fusion.9 = f32[32,64,128] fusion()", 60 * ms, 90 * ms),
           ("%fusion.2 = f32[8192,2048] fusion()", 90 * ms, 200 * ms)]
    tf_op = {ops[0][0]: "jit(step)/jvp(lm/layers)/gdn/recurrence/gdn_scan/"
                        "pallas_call",
             ops[1][0]: "jit(step)/transpose(jvp(lm/layers))/gdn/recurrence"
                        "/gdn_scan/dot_general",
             ops[2][0]: "jit(step)/gdn/recurrence/gdn_scan/while",
             ops[3][0]: "jit(step)/gdn/recurrence/gdn_scan/while/body/mul",
             ops[4][0]: "jit(step)/jvp(lm/layers)/gdn/project/dot_general"}
    chip = types.SimpleNamespace(modules=[("jit_step(1)", 0, 200 * ms)],
                                 ops=ops)
    run.trace_reduction = types.SimpleNamespace(chips=[chip])
    run.tracer = types.SimpleNamespace(xplane_path=lambda: "a.xplane.pb")

    gdn = harness._load_reader(harness.Cell(CELL), "gdn_scan_roofline")
    monkeypatch.setattr(
        gdn.__globals__["routed"], "operation_strings",
        lambda path: {name: {"tf_op": op} for name, op in tf_op.items()})
    share = gdn(run)
    # 90 ms under the scope in one step (the loop's own event left out)
    assert share == pytest.approx(100 * 3 * 2.637e-3 / 0.090, rel=1e-3)
    assert "bound by bytes forward and bytes backward" \
        in capsys.readouterr().out
    tf_op.update({name: "jit(step)/lm/layers" for name in tf_op})
    assert gdn(run) is None                    # a program without the scope
