"""``run.py``'s contract at the edges: it refuses a CPU, an unknown kind
of TPU and too few chips; every cell of ``BENCHMARK.json`` finds its
files; names and units keep to the allowed characters."""
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


class FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_run_refuses_a_cpu():
    cell = bench()["workloads"][0]["name"]
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert '"metrics"' not in out.stdout and '"correct"' not in out.stdout


@pytest.mark.parametrize("devices,why", [
    ([FakeDevice("tpu", "TPU v9 unheard of")], "not in benchmark/peaks"),
    ([FakeDevice("cpu", "cpu")], "needs a TPU"),
    ([], None),
])
def test_require_chip_refuses(monkeypatch, devices, why):
    import jax

    cell = harness.Cell(bench()["workloads"][0]["name"])
    monkeypatch.setattr(jax, "devices", lambda *a: devices or
                        [FakeDevice("tpu", "TPU v5 lite")])
    if not devices:                      # a known chip, but too few
        cell.chips = 4
        why = "needs 4 chips"
    with pytest.raises(SystemExit) as e:
        harness.require_chip(cell)
    assert why in str(e.value)


def test_require_chip_accepts_the_v5e(monkeypatch):
    import jax

    cell = harness.Cell(bench()["workloads"][0]["name"])
    four = [FakeDevice("tpu", "TPU v5 lite")] * 4
    monkeypatch.setattr(jax, "devices", lambda *a: four)
    assert len(harness.require_chip(cell)) == cell.chips


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        harness.Cell("no.such.cell")


@pytest.mark.parametrize("row", bench()["workloads"],
                         ids=lambda w: w["name"])
def test_cell_finds_its_files(row):
    cell = harness.Cell(row["name"])
    assert cell.workload["config"] == row["config"]
    assert cell.workload["traffic"] == row["traffic"]
    assert cell.workload["chips"] == row["chips"] == cell.chips
    assert os.path.exists(os.path.join(
        harness.HERE, "entries", cell.workload["entry"] + ".py"))
    reported = {m["name"] for m in cell.metric_rows("end_to_end")}
    assert "setup_s" in reported and len(reported) >= 2
    layer = cell.metric_rows("per_layer")
    assert layer
    for m in layer:
        assert m["moves"] in reported, (m["name"], m["moves"])
        assert callable(harness._load_reader(cell, m["name"]))


def test_a_split_quantity_has_one_reader(tiny_cell):
    """``device_idle_pct.train`` and ``device_idle_pct.serve`` report
    different end-to-end metrics, so they are two rows; both are read by
    ``metrics/device_idle_pct.py``.  A name with no reader is refused."""
    cell = tiny_cell("train.gpt2-tiny.cpu")
    a = harness._load_reader(cell, "device_idle_pct.train")
    b = harness._load_reader(cell, "device_idle_pct.serve")
    assert a.__code__.co_filename == b.__code__.co_filename
    assert a.__code__.co_filename.endswith("metrics/device_idle_pct.py")
    assert not os.path.exists(os.path.join(
        harness.HERE, "metrics", "device_idle_pct.train.py"))
    with pytest.raises(SystemExit):
        harness._load_reader(cell, "no_such_metric.train")


def test_what_the_reference_compiles_is_not_setup(tiny_cell):
    """``compile_s`` moves ``setup_s``, so it leaves out what
    ``setup_s`` leaves out: the seconds, and the compilation, of the
    reference; and the collector is held off inside the window only."""
    import gc
    import time

    import jax

    run = harness.Run(tiny_cell("train.gpt2-tiny.cpu"), 1, 1.0, False,
                      jax.devices()[:1], time.perf_counter())
    run.watch._duration(run.watch._COMPILE, 2.0)      # the program's
    with run.outside_setup():
        run.watch._duration(run.watch._COMPILE, 5.0)  # the reference's
        time.sleep(0.05)
    run.begin_window()
    assert not gc.isenabled()
    assert run.counters["setup_compile"]["compile_s"] == pytest.approx(2.0)
    assert run.counters["setup_compile"]["compiles"] == 1
    assert run.not_counted_s >= 0.05
    run.end_window()
    assert gc.isenabled()
    assert run.counters["window_compile"]["compiles"] == 0


def test_benchmark_json_keeps_to_the_contract():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in b["configs"]:
        assert c["file"].startswith(b["paths"][0] + "/")
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["reduced"] == c["reduced"]
        assert not any(k.endswith(("_dim", "_rank")) or k in (
            "n_embd", "n_inner", "n_head") for k in c["reduced"])


def test_percentile_and_histogram_helpers():
    assert harness.percentile(list(range(1, 101)), 95) == 95
    assert harness.percentile([], 95) is None
    from benchmark.entries.serve import histogram_counts

    text = ('x_bucket{le="0.1"} 3\nx_bucket{le="1.0"} 9\n'
            'x_bucket{le="+Inf"} 10\nx_count 10\n')
    assert histogram_counts(text, "x") == {0.1: 3.0, 1.0: 9.0,
                                           float("inf"): 10.0}
