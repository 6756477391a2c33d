"""What the SDAR-30B-A3B-Chat share brings to the benchmark: its cell's
files, ``flops_bd_moe.py`` against a count by hand, the new reference
deciding ``correct`` at test size (a sound run, the timed path broken
underneath, another MASK in the program's place, the controls), the
reference's mask and noise by its four rules, and the new readers against
a run that has nothing for them and against a cut of the cell's recorded
trace."""
import dataclasses
import functools
import importlib
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark.run as brun
from benchmark import flops_bd_moe as flops
from benchmark import harness, xplane
from benchmark.entries import train
from benchmark.reference import sdar

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train.sdar-30b-a3b-chat.ep8-share.bd4.seq8192"
DATA = os.path.join(HERE, "data_sdar")
TINY = "train.sdar-tiny.cpu"
CUT = os.path.join(HERE, "traces_scoped", CELL + ".cut.xplane.pb")
PEAKS = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
NEW_READERS = ("bd_moe_train_mfu_pct", "block_diffusion_attention_roofline",
               "bd_attention_device_pct",
               "bd_pairs_computed_per_attended_pair")


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
    assert cell.config["reference"] == "sdar_30b_a3b_chat_ep8_share"
    assert callable(train.reference_module(cell.config).train_steps)
    assert cell.traffic == {**cell.traffic, "kind": "lm_batches",
                            "seq_len": 8192, "global_batch": 1}
    assert set(cell.workload["limits"]) == {
        "loss_gap_max", "first_grad_norm_gap_worst_leaf",
        "param_change_norm_gap_worst_leaf", "first_grad_sample_rel_err"}
    rows = {m["name"] for m in cell.metric_rows("per_layer")}
    assert set(NEW_READERS) <= rows
    assert {"compile_s", "train_step_ms_p50", "device_idle_pct.train",
            "hbm_peak_gb.train", "moe_routed_device_pct",
            "moe_rows_computed_per_routed_row", "step_unscoped_device_pct",
            "step_recomputed_device_pct"} <= rows
    assert not {"train_mfu_pct", "flash_attention_roofline",
                "dsa_moe_train_mfu_pct", "sparse_attention_roofline",
                "collective_exposed_pct"} & rows
    assert {m["name"] for m in cell.metric_rows("end_to_end")} == {
        "train_tokens_s_chip", "setup_s"}
    for other in ("train.gpt2-medium.1chip",
                  "train.keye-vl-2.0-30b-a3b.ep8-share.seq16384"):
        assert not set(NEW_READERS) & {m["name"] for m in harness.Cell(
            other).metric_rows("per_layer")}


def test_the_benchmark_lists_the_cell_where_it_reports():
    """What this cell needs of ``BENCHMARK.json``, and nothing a later cell
    would break: no count, no position."""
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "pretrain-seq8192-b1")
    assert cell["config"] in {c["name"] for c in bench["configs"]}
    # four-chip cells within the quarter the contract allows (one always)
    assert [w["chips"] for w in bench["workloads"]].count(4) <= max(
        1, len(bench["workloads"]) // 4)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["train_tokens_s_chip"]["workloads"]
    assert "workloads" not in e2e["setup_s"]
    rows = {m["name"]: m for m in bench["per_layer"]}
    for name in ("compile_s", "train_step_ms_p50", "device_idle_pct.train",
                 "hbm_peak_gb.train", "moe_routed_device_pct",
                 "moe_rows_computed_per_routed_row",
                 "step_unscoped_device_pct", "step_recomputed_device_pct"):
        assert CELL in rows[name]["workloads"]
    for name in NEW_READERS:
        assert rows[name]["workloads"] == [CELL]
        assert rows[name]["moves"] == "train_tokens_s_chip"


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
                              "vocab_size"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["rope_theta"], cfg["rms_norm_eps"]) == (
        2048, 32, 4, 128, 768, 8, 1000000, 1e-6)
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == 8
    assert dep["experts_held"] == [0, cfg["num_experts"]] == [0, 16]
    assert dep["num_experts_published"] == 128
    assert cfg["vocab_size"] * 8 == dep["vocab_size_published"] == 151936
    assert 4 <= cfg["num_hidden_layers"] <= 6
    kw, bd = cfg["program"]["kwargs"], cfg["block_diffusion"]
    assert (kw["d_model"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"], kw["d_expert"], kw["num_experts"],
            kw["top_k"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["moe_intermediate_size"], 128, cfg["num_experts_per_tok"])
    assert (kw["rope_theta"], kw["rms_eps"]) == (
        cfg["rope_theta"], cfg["rms_norm_eps"])
    assert (kw["block_length"], kw["noise_eps"], kw["noise_seed"],
            kw["mask_id"]) == (bd["block_length"], bd["noise_eps"],
                               bd["noise_seed"], bd["mask_token_id"]) \
        == (4, 1e-3, 0, cfg["vocab_size"] - 1)
    assert kw["vocab_size"] == cfg["vocab_size"]
    assert kw["num_layers"] == cfg["num_hidden_layers"]
    assert kw["experts_held"] == dep["experts_held"]
    assert kw["train_router"] is cfg["train_router"] is False
    assert kw["embed_scale"] == cfg["embed_scale"] == 10000.0
    assert any("embed_scale" in d for d in cfg["departures"])
    assert set(cfg["assumed"]) >= {"block_length", "noise_schedule",
                                   "level_per_block", "mask_token",
                                   "qk_norm", "noise_rule", "weights"}
    assert len(cfg["departures"]) >= 3


def test_flops_against_a_count_by_hand():
    cfg = harness.Cell(CELL).config
    layers = cfg["num_hidden_layers"]
    # W_q and W_o 2048 x 32 x 128, W_k and W_v 2048 x 4 x 128
    attn = 2 * 8_388_608 + 2 * 1_048_576
    assert flops.attention_params(cfg) == attn == 18_874_368
    assert flops.expert_params(cfg) == 3 * 2048 * 768 == 4_718_592
    assert flops.held_share(cfg) == 0.125
    # what the program's init makes, to the parameter
    spec = train.build_spec(cfg)
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    assert flops.total_params(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == layers * 94_638_336 + 77_791_232 + 2048
    assert cfg["parameters"]["attention_a_layer"] == attn + 256
    assert f"{flops.total_params(cfg):,}" in cfg["parameters"]["note"]
    # a layer and row: attention, the router's 128 outputs, 8 x 1/8 of an
    # expert
    a_layer = attn + 262_144 + 4_718_592
    assert flops.layer_matmul_params(cfg) == pytest.approx(a_layer)
    # L (L + B) pairs a head: 67.1 M where the causal triangle of the
    # 16,384 rows has 134.2 M
    assert flops.attended_pairs(cfg, 8192) == 8192 * 8196 == 67_141_632
    # one layer's forward over the 16,384 rows, in TFLOP: attention 1.10,
    # projections 0.62, the held experts 0.15
    f, _ = flops.block_attention_call(1, cfg, 8192, 4, backward=False)
    assert f == 4 * 128 * 32 * 67_141_632
    assert f / 1e12 == pytest.approx(1.100, abs=1e-3)
    assert 2 * attn * 16384 / 1e12 == pytest.approx(0.618, abs=1e-3)
    assert 2 * 4_718_592 * 16384 / 1e12 == pytest.approx(0.155, abs=1e-3)
    # a data token: two rows through the layers, one through the head
    attention = layers * 3 * 4 * 128 * 32 * 8196
    per_token = flops.train_flops_per_token(cfg, 8192)
    assert per_token == pytest.approx(
        6 * (2 * layers * a_layer + 18992 * 2048) + attention)
    assert flops.routed_flops_per_token(cfg) \
        == 6 * 2 * layers * 4_718_592
    # the step: 8,192 data tokens
    assert per_token * 8192 / 1e12 == pytest.approx(
        {4: 24.49, 5: 30.14, 6: 35.78}[layers], abs=0.02)


def test_block_attention_call_counts_the_masks_pairs():
    cfg = harness.Cell(CELL).config
    pairs = 32 * 67_141_632
    rows = 16384 * 128 * 4
    f, b = flops.block_attention_call(1, cfg, 8192, 4, backward=False)
    assert f == 4 * 128 * pairs and b == rows * (64 + 8)
    f, b = flops.block_attention_call(1, cfg, 8192, 4, backward=True)
    assert f == 10 * 128 * pairs and b == rows * (128 + 16)
    # the chip's FLOPs bind, not its bytes; 19.5 ms a layer in all
    assert f / 197e12 > b / 819e9
    least = sum(flops.block_attention_call(1, cfg, 8192, 4, backward=x)[0]
                for x in (False, True)) / 197e12
    assert least * 1e3 == pytest.approx(19.54, abs=2e-2)
    two = flops.block_attention_call(2, cfg, 8192, 4, backward=True)
    assert two == (2 * f, 2 * b)


# ---------------------------------------------------------------------------
# the reference's own rules
# ---------------------------------------------------------------------------

def tiny_cell():
    return harness.Cell(TINY, root=DATA)


def tiny_settings():
    return train.reference_module(tiny_cell().config).SETTINGS


def test_the_reference_builds_its_mask_from_the_four_rules():
    s = dataclasses.replace(tiny_settings(), block_length=3)
    length = 12
    r = jnp.arange(2 * length)
    got = np.asarray(sdar.sees(r[:, None], r[None, :], length, s))
    for q in range(2 * length):
        for k in range(2 * length):
            if q < length and k < length:
                want = k // 3 <= q // 3
            elif q < length:
                want = False
            elif k < length:
                want = k // 3 < (q - length) // 3
            else:
                want = (k - length) // 3 == (q - length) // 3
            assert got[q, k] == want, (q, k)
    # a clean row sees its whole block, later rows included; a noised row
    # sees its own block both ways and nothing clean of it
    assert got[3, 5] and not got[3, 6]
    assert got[length + 4, length + 5] and got[length + 5, length + 4]
    assert not got[length + 4, 3] and got[length + 4, 2]
    assert got.sum() == 2 * (length * (length + 3) // 2)
    causal = dataclasses.replace(s, wrong="causal_mask")
    assert np.array_equal(
        np.asarray(sdar.sees(r[:, None], r[None, :], length, causal)),
        np.tril(np.ones((2 * length, 2 * length), bool)))


def test_the_reference_draws_its_noise_by_the_stated_rule():
    s = tiny_settings()
    x = jnp.asarray(np.random.default_rng(1).integers(0, 61, (96,)),
                    jnp.int32)
    copy, weight = sdar.noised(x, s)
    copy, weight = np.asarray(copy), np.asarray(weight)
    masked = weight > 0
    assert 0 < masked.sum() < 96
    assert (copy[masked] == s.mask_id).all()
    assert (copy[~masked] == np.asarray(x)[~masked]).all()
    # one level a block: the masked tokens of a block share a weight 1 / t
    # with eps <= t <= 1
    for b in range(96 // s.block_length):
        w = weight[b * s.block_length:(b + 1) * s.block_length]
        assert len(set(w[w > 0].tolist())) <= 1
    assert (weight[masked] >= 1.0).all() \
        and (weight[masked] <= 1.0 / s.noise_eps).all()
    # a function of the sequence's own tokens and the seed, nothing else
    again = sdar.noised(x, s)
    assert np.array_equal(np.asarray(again[0]), copy)
    other = sdar.noised(x.at[7].set((x[7] + 1) % 61), s)
    assert not np.array_equal(np.asarray(other[1]), weight)
    reseeded = sdar.noised(x, dataclasses.replace(s, noise_seed=6))
    assert not np.array_equal(np.asarray(reseeded[1]), weight)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The held experts' part only: over the eight shares of a layer's 16
    experts (two each here) the parts add up to the layer with every
    expert held."""
    cfg = tiny_cell().config
    s = tiny_settings()
    rng = np.random.default_rng(3)
    d, f, total = 32, 12, 16
    x = jnp.asarray(rng.standard_normal((24, d)), jnp.float32)
    whole = {"router": jnp.asarray(rng.standard_normal((d, total)),
                                   jnp.float32),
             "experts": {k: jnp.asarray(
                 rng.standard_normal((total,) + shape) * 0.3, jnp.float32)
                 for k, shape in (("w_gate", (d, f)), ("w_up", (d, f)),
                                  ("w_down", (f, d)))}}
    uncut = sdar._experts(x, whole, dataclasses.replace(s, first_held=0),
                          None)
    parts = 0.0
    for share in range(8):
        held = dict(whole, experts={k: v[2 * share:2 * share + 2]
                                    for k, v in whole["experts"].items()})
        parts = parts + sdar._experts(
            x, held, dataclasses.replace(s, first_held=2 * share), None)
    assert cfg["deployment"]["num_experts_published"] == total
    assert float(jnp.max(jnp.abs(parts - uncut))) < 1e-5
    assert float(jnp.max(jnp.abs(uncut))) > 1e-2


def test_the_reference_keeps_to_plain_jax():
    """No kernel, no grouped product, no tile, nothing of the program;
    the mask by ``//`` on index grids."""
    with open(os.path.join(os.path.dirname(HERE), "reference",
                           "sdar.py")) as f:
        code = f.read().split('"""', 2)[2]
    for word in ("autodist_tpu", "ragged_dot", "pallas", "argsort",
                 "jnp.sort", "lax.sort", "fori_loop", "bitcast", ">>",
                 "block_diffusion_mask", "noise_of"):
        assert word not in code, word
    assert "c // b <= r // b" in code and "jax.random.fold_in(" in code


# ---------------------------------------------------------------------------
# ``correct`` with the new reference, at test size
# ---------------------------------------------------------------------------

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


def _another_program(monkeypatch, **changed):
    """The program built with other arguments than the configuration (and
    so the reference) states."""
    build = train.build_spec

    def other(config):
        config = json.loads(json.dumps(config))
        config["program"]["kwargs"].update(changed)
        return build(config)

    monkeypatch.setattr(train, "build_spec", other)


def _causal_attention(q, k, v, causal, *, block_diffusion):
    from autodist_tpu.models.transformer import dense_selected_attention

    return dense_selected_attention(q, k, v, True)


@pytest.mark.parametrize("broken,failing", [
    ("frozen", "param_change_norm_gap_worst_leaf"),
    ("rows_left_out", "loss_gap_max"),
    ("causal", "first_grad_sample_rel_err"),   # the MASK wrong
    ("blocks_of_8", "first_grad_sample_rel_err"),
    ("other_noise", "loss_gap_max"),           # another draw of the noise
])
def test_broken_step_is_not_correct(monkeypatch, capsys, broken, failing):
    if broken == "causal":
        model = importlib.import_module("autodist_tpu.models.gqa_bd_moe_lm")
        monkeypatch.setattr(
            model, "gqa_bd_moe_lm", functools.partial(
                model.gqa_bd_moe_lm, attn_fn=_causal_attention))
    elif broken == "blocks_of_8":
        _another_program(monkeypatch, block_length=8)
    elif broken == "other_noise":
        _another_program(monkeypatch, noise_seed=6)
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


@pytest.mark.parametrize("compute", ["bfloat16", "int8", "causal_mask"])
def test_control_leaves_the_tolerance(compute):
    """The reference in a lower precision, or under the plain causal mask
    over the 2 L rows, in the program's place, judged as the program is,
    fails one of the cell's numbers on every seed."""
    from benchmark import traffic, weights

    cell = tiny_cell()
    ref = train.reference_module(cell.config)
    spec = train.build_spec(cell.config)
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    stand_in = dict(s=dataclasses.replace(ref.SETTINGS, wrong=compute)) \
        if compute == "causal_mask" else dict(s=ref.SETTINGS, compute=compute)
    failed = 0
    for seed in (1, 2, 3):
        batches = traffic.lm_batches(cell.traffic, 61, seed)
        check = [jnp.asarray(next(batches)) for _ in range(3)]
        p0 = ref.to_reference(weights.make_weights(shapes, seed))
        want = ref.train_steps(p0, check, row_block=2, sample_seed=seed)
        ctl = sdar.train_steps(p0, check, row_block=2, sample_seed=seed,
                               **stand_in)
        pooled, _ = train.sample_errors(ctl[3], want[3])
        numbers = {
            "product_operands_narrower_than_stated":
                train.narrow_product_operands(
                    sdar.lowered_block_grad(
                        p0, check[0][:2], stand_in.get("compute", "float32"),
                        s=stand_in["s"]), "float32"),
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


def test_the_kernels_take_and_give_the_stated_type():
    """``narrow_product_operands`` counts every operand of a Pallas call
    narrower than float32: the step of the program with its kernel,
    lowered for the TPU, counts 0, and its attention is two calls a layer
    (a forward and a fused backward)."""
    flash = importlib.import_module("autodist_tpu.ops.flash_attention")
    model = importlib.import_module("autodist_tpu.models.gqa_bd_moe_lm")
    # the tiny widths at a length the TPU's tiles divide (nothing runs)
    kwargs = dict(tiny_cell().config["program"]["kwargs"], dtype=jnp.float32,
                  seq_len=256, block_k=256, moe_slice=512)
    spec = model.gqa_bd_moe_lm(**kwargs, attn_fn=functools.partial(
        flash.flash_attention, interpret=False, block_k=256))
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    text = jax.jit(jax.grad(spec.loss_fn)).trace(
        shapes, {"tokens": jax.ShapeDtypeStruct((1, 256), jnp.int32)},
    ).lower(lowering_platforms=("tpu",)).as_text()
    calls = [ln for ln in text.splitlines() if "@tpu_custom_call" in ln]
    # a forward and a fused backward a layer, over the 2 L rows
    assert len(calls) == 4 and all("x512x16xf32>" in ln for ln in calls)
    assert train.narrow_product_operands(text, "float32") == 0


# ---------------------------------------------------------------------------
# the new readers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_readers_return_none_on_a_gpt2_run(metric):
    """An untraced run of a GPT-2 cell against a program registry that
    holds no gauges of the block-diffusion attention: every new reader
    returns None and raises nothing (the parent commit's side of a traced
    run)."""
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
    run.counters.update(global_batch=1, seq_len=8192)
    return run


def test_mfu_and_pairs_from_counters():
    from autodist_tpu.telemetry import registry

    run = harness.Run(harness.Cell(CELL), 1, 1.0, False, jax.devices()[:1],
                      time.perf_counter())
    run.peaks = PEAKS
    run.counters.update(global_batch=1, seq_len=8192, steps=20,
                        tokens_per_step=8192, step_s=[1.0] * 20)
    per_token = flops.train_flops_per_token(run.cell.config, 8192)
    assert reader("bd_moe_train_mfu_pct")(run) == pytest.approx(
        100 * 8192 * per_token / 197e12)
    registry.reset_for_testing()
    assert reader("bd_pairs_computed_per_attended_pair")(run) is None
    for kind, pairs in (("computed", 288 * 512 * 512),
                        ("attended", 67_141_632)):
        registry.gauge("autodist_bd_pairs_per_step", "", {"kind": kind}
                       ).set(4 * 32 * pairs)
    assert reader("bd_pairs_computed_per_attended_pair")(run) \
        == pytest.approx(1.1245, abs=1e-3)
    registry.reset_for_testing()


def test_readers_on_the_recorded_step(monkeypatch, capsys):
    """A cut of the cell's traced run on the v5e (my chip run, PR 47: one
    whole step, operations of 100 us or more and every kernel, each with
    its ``tf_op``): eight ``bd_attn`` calls a step (a forward and a fused
    backward for each of 4 layers, none run twice), bound by FLOPs both
    ways; the attention by its scope; the routed layer's own scopes read as
    in the keye cell."""
    run = traced_run(monkeypatch)
    steps, calls, seconds = run.trace_reduction.ops_in_module_runs(
        r"^jit_step\b", r'^%?bd_attn[\w.\-]* = .*tpu_custom_call')
    assert (steps, calls) == (1, 8)
    share = reader("block_diffusion_attention_roofline")(run)
    assert share == pytest.approx(100 * 4 * 19.544e-3 / seconds, rel=1e-3)
    assert 40.0 < share < 100.0
    attention = reader("bd_attention_device_pct")(run)
    assert 25.0 < attention < 50.0
    routed = reader("moe_routed_device_pct")(run)
    assert 5.0 < routed < 40.0
    out = capsys.readouterr().out
    assert "bound by flops forward and flops backward" in out
    for scope in ("bd/attention", "gqa/project", "moe/route", "moe/experts",
                  "moe/combine", "lm/head_loss"):
        assert scope in out
