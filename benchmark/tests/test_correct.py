"""``correct`` can come out false.

Two kinds of test, at a size a test run can hold (the cells under
``tests/data``, CPU):

* the CONTROL: the reference computed in bfloat16, the nearest precision
  below the one the configurations state, put in the program's place,
  leaves the tolerance;
* the timed path BROKEN underneath a whole run (the chip check skipped,
  the rest of ``run.py`` driven): a step that returns its state
  unchanged, a step that leaves out half of the batch (on four devices:
  one device's rows), a token altered where it is produced.  The broken
  sessions are ``control.py``'s, which reads them on the chip at a cell's
  own size.
"""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark.run as brun
from benchmark import harness
from benchmark.control import Frozen, RowsLeftOut
from benchmark.entries import serve, train


def drive(cell, seed=2**31 + 5, seconds=1.0):
    """``run.py`` after its look for a chip."""
    return brun.run_cell(cell, seed, seconds, False,
                         jax.devices()[:cell.chips], time.perf_counter())


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def test_train_sound_run_is_correct(tiny_cell, capsys):
    line = drive(tiny_cell("train.gpt2-tiny.cpu"))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"train_tokens_s_chip", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    out = capsys.readouterr().out       # each number beside its limit
    for name in ("loss_gap_max", "product_operands_narrower_than_stated",
                 "first_grad_norm_gap_worst_leaf",
                 "param_change_norm_gap_worst_leaf",
                 "first_grad_sample_rel_err"):
        assert f"check {name}:" in out
        assert line["checks"][name]["ok"] is True
    assert list(line)[-1] == "checks"   # and last in the result's line
    harness.emit(line)                  # and as the last lines of stderr
    io = capsys.readouterr()
    assert io.err.splitlines()[-1].startswith("check window_losses")
    assert json.loads(io.out.splitlines()[-1]) == line


def test_train_on_a_data_axis_of_four_is_correct(tiny_cell):
    """The dp4 cell's path at test size: four (virtual) devices, the
    batch split over them, the same comparison with the reference."""
    line = drive(tiny_cell("train.gpt2-tiny.cpu4"))
    assert line["correct"] is True
    assert line["device"]["count"] == 4


@pytest.mark.parametrize("cell", ["train.gpt2-tiny.cpu",
                                  "train.gpt2-tiny.cpu4"])
@pytest.mark.parametrize("broken,failing", [
    (Frozen, "param_change_norm_gap_worst_leaf"),
    (RowsLeftOut, "loss_gap_max"),
])
def test_train_broken_step_is_not_correct(tiny_cell, monkeypatch, capsys,
                                          broken, failing, cell):
    build = train.build_session
    monkeypatch.setattr(train, "build_session",
                        lambda *a, **k: broken(build(*a, **k)))
    line = drive(tiny_cell(cell))
    assert line["correct"] is False
    out = capsys.readouterr().out
    bad = [ln for ln in out.splitlines() if "NOT CORRECT" in ln]
    assert any(failing in ln for ln in bad), out
    assert line["checks"][failing]["ok"] is False


def test_rows_left_out_are_one_devices_rows():
    """On one device the second half of the batch, on four the last
    device's rows; the first rows take their place."""
    class Sess:
        def __init__(self, n):
            self.mesh = type("M", (), {"size": n})

        def run(self, batch):
            return batch["tokens"][:, 0].tolist()

    tokens = np.arange(8)[:, None] * np.ones((1, 3), np.int32)
    assert RowsLeftOut(Sess(1)).run({"tokens": tokens}) == [
        0, 1, 2, 3, 0, 1, 2, 3]
    assert RowsLeftOut(Sess(4)).run({"tokens": tokens}) == [
        0, 1, 2, 3, 4, 5, 0, 1]


CONTROLS = pytest.mark.parametrize(
    "compute", [jnp.bfloat16, "int8"], ids=["bfloat16", "int8"])


@CONTROLS
def test_train_control_leaves_the_tolerance(tiny_cell, compute):
    """The reference in a lower precision in the program's place, judged
    as the program is: it has to fail one of the cell's numbers.  (The
    test-size configuration states float32, whose rung below is bfloat16;
    the cells on the chip multiply in bfloat16, whose rung below is
    int8.)"""
    cell = tiny_cell("train.gpt2-tiny.cpu")
    from benchmark import traffic, weights
    from benchmark.reference import gpt2

    spec = train.build_spec(cell.config)
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    failed = 0
    for seed in (1, 2, 3):
        batches = traffic.lm_batches(cell.traffic, 257, seed)
        check = [jnp.asarray(next(batches)) for _ in range(3)]
        p0 = weights.make_weights(shapes, seed)
        p0 = gpt2.to_reference(p0)
        ref = gpt2.train_steps(p0, check, row_block=2, sample_seed=seed)
        ctl = gpt2.train_steps(p0, check, row_block=2, compute=compute,
                               sample_seed=seed)
        pooled, worst = train.sample_errors(ctl[3], ref[3])
        numbers = {
            "product_operands_narrower_than_stated":
                train.narrow_product_operands(
                    gpt2.lowered_block_grad(p0, check[0][:2], compute),
                    "float32"),
            "first_grad_sample_rel_err": pooled,
            "loss_gap_max": max(abs(a - b) for a, b in zip(ctl[0], ref[0])),
            "first_grad_norm_gap_worst_leaf":
                train.worst_leaf_gap(ctl[1], ref[1]),
            "param_change_norm_gap_worst_leaf":
                train.worst_leaf_gap(ctl[2], ref[2])}
        limits = dict(cell.workload["limits"],
                      product_operands_narrower_than_stated=0)
        failed += any(numbers[k] > limits[k] for k in numbers)
    assert failed == 3


def test_types_of_products_tell_bfloat16_storage_exactly(tiny_cell):
    """The lowered program's products and kernels take and give what the
    configuration states; the reference stored in bfloat16, lowered in
    the program's place, does not, and a table stated to be narrower is
    left out by its dims."""
    cell = tiny_cell("train.gpt2-tiny.cpu")
    from benchmark import weights
    from benchmark.reference import gpt2

    spec = train.build_spec(cell.config)
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    p0 = gpt2.to_reference(weights.make_weights(shapes, 1))
    tokens = jnp.zeros((2, 64), jnp.int32)
    count = {c: train.narrow_product_operands(
        gpt2.lowered_block_grad(p0, tokens, c), "float32")
        for c in ("float32", "bfloat16_products", "int8", "bfloat16")}
    assert count["float32"] == count["bfloat16_products"] == 0
    assert count["int8"] == 0       # fake quantization: numbers catch it
    assert count["bfloat16"] > 20
    text = ('%1 = stablehlo.dot_general %a, %b, contracting_dims = [2] x '
            '[1] : (tensor<4x8x16xf32>, tensor<257x16xbf16>) -> '
            'tensor<4x8x257xf32>\n'
            '%2 = stablehlo.custom_call @tpu_custom_call(%q) {backend_config'
            ' = "x : (y"} : (tensor<2x8x4x16xbf16>, tensor<2xi32>) -> '
            '(tensor<2x8x4x16xf32>, tensor<2x8x4x1xf8E4M3FN>)\n'
            '%3 = stablehlo.add %x, %y : (tensor<4xbf16>) -> tensor<4xbf16>')
    assert train.narrow_product_operands(text, "float32") == 3
    assert train.narrow_product_operands(text, "float32", ("257x16",)) == 2
    assert train.narrow_product_operands(text, "bfloat16") == 1
    assert train.stated_tables(cell.config) == ()
    assert train.stated_tables(dict(
        cell.config, precision={"operands": "float32",
                                "tables": "bfloat16"})) == ("257x64",)


def test_worst_leaf_gap_takes_the_norms_gap_against_the_larger_floor():
    want = {"a": 1.0, "b": 2.0, "c": 1e-9}
    got = {"a": 1.1, "b": 2.0, "c": 2e-9}
    # leaf c is all but zero: its gap is measured against the median (1.0)
    assert train.worst_leaf_gap(got, want) == pytest.approx(0.1)
    with pytest.raises(SystemExit):
        train.worst_leaf_gap({"a": 1.0}, want)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def test_serve_sound_run_is_correct(tiny_cell):
    line = drive(tiny_cell("serve.gpt2-tiny.cpu"), seconds=3.0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 18
    assert set(line["metrics"]) == {
        "serve_ttft_ms_p95", "serve_tpot_ms_p95", "serve_out_tokens_s",
        "setup_s"}


def test_serve_altered_token_is_not_correct(tiny_cell, monkeypatch, capsys):
    from autodist_tpu.serving.scheduler import PagedDecodeEngine

    sound = PagedDecodeEngine._slot_tokens

    def altered(self, b, req):
        seq = np.array(sound(self, b, req))
        p = req.prompt.size - req.strip
        seq[p:] = (seq[p:] + 1) % 257      # every served token, off by one
        return seq

    monkeypatch.setattr(PagedDecodeEngine, "_slot_tokens", altered)
    line = drive(tiny_cell("serve.gpt2-tiny.cpu"), seconds=3.0)
    assert line["correct"] is False
    assert "check served_gap_max" in capsys.readouterr().out


@CONTROLS
def test_serve_control_leaves_the_tolerance(tiny_cell, compute):
    cell = tiny_cell("serve.gpt2-tiny.cpu")
    from benchmark import weights

    spec = train.build_spec(cell.config)
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    failed = 0
    for seed in (1, 2, 3):
        params = weights.make_weights(shapes, seed)
        rng = np.random.default_rng(seed)
        samples = [(rng.integers(0, 257, 16).tolist(),
                    rng.integers(0, 257, 48).tolist()) for _ in range(8)]
        got = serve.served_gaps(params, samples, 64, control=compute)
        lim = cell.workload["limits"]
        failed += (got["gap_max"] > lim["served_gap_max"]
                   or got["gap_mean"] > lim["served_gap_mean"])
    assert failed == 3


def test_prefill_shapes_cover_what_the_mix_can_form():
    mix = {"prompt_len": {"dist": "lognormal", "median": 256, "sigma": 0.7,
                          "min": 32, "max": 768}}
    eng = {"slots": 16, "window": 1024, "prefill_chunk": 256}
    shapes = serve.prefill_shapes(mix, eng)
    assert {pb for _, pb in shapes} == {1, 2, 4, 8, 16, 32, 64, 128, 256}
    assert {k for k, _ in shapes} == {1, 2, 4, 8, 16}
    whole = serve.prefill_shapes(mix, dict(eng, prefill_chunk=None))
    assert {pb for _, pb in whole} == {32, 64, 128, 256, 512, 1024}


def test_summarize_counts_failures_and_the_window():
    recs = [
        {"i": 0, "status": "ok", "due_s": 0.0, "sent_s": 0.001,
         "first_s": 0.2, "done_s": 1.2, "asked": 11, "prompt_len": 5},
        {"i": 1, "status": "ok", "due_s": 1.0, "sent_s": 1.0,
         "first_s": 1.5, "done_s": 4.5, "asked": 4, "prompt_len": 5},
        {"i": 2, "status": "http_429", "due_s": 2.0, "sent_s": 2.0,
         "done_s": 2.0, "asked": 4, "prompt_len": 5},
        {"i": 3, "status": "unfinished", "due_s": 2.5, "sent_s": 2.5,
         "asked": 4, "prompt_len": 5},
    ]
    s = serve.summarize(recs, 3.0)
    assert (s["sent"], s["ok"], s["failed"], s["unfinished"]) == (4, 2, 1, 1)
    assert s["out_tokens_in_window"] == 11      # the second ends too late
    assert s["ttft_ms"] == pytest.approx([200.0, 500.0])
    assert s["tpot_ms"] == pytest.approx([100.0, 1000.0])
    assert s["backlog_mid"] == 1 and s["backlog_end"] == 2
