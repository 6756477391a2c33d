"""Analyzer CLI smoke tests (tier-1, CPU-only, fast).

The CLI contract the acceptance criteria pin: a deliberately illegal
strategy (non-divisible partition on the 8-device virtual mesh) exits
nonzero with a rule-tagged diagnostic in seconds, while the shipped
example models × builders come out clean — including the
``examples/linear_regression.py`` and pipeline-example shapes.
"""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from autodist_tpu.graph_item import GraphItem
from autodist_tpu.strategy.base import (
    PSSynchronizerConfig,
    Strategy,
    VarConfig,
)

pytestmark = pytest.mark.analysis


def _run_cli(*args, timeout=60):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "autodist_tpu.analysis", *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _illegal_strategy_files(tmp_path):
    """A non-divisible partition on the 8-device virtual mesh."""
    gi = GraphItem({"w": jax.ShapeDtypeStruct((3, 4), jnp.float32),
                    "b": jax.ShapeDtypeStruct((4,), jnp.float32)})
    strategy = Strategy(node_config=[
        VarConfig("w", synchronizer=PSSynchronizerConfig(),
                  partitioner="3,1"),
        VarConfig("b", synchronizer=PSSynchronizerConfig())])
    spath = tmp_path / "strategy.json"
    spath.write_text(json.dumps(strategy.to_dict()))
    cpath = tmp_path / "catalog.json"
    cpath.write_text(gi.serialize())
    return str(cpath), str(spath)


def test_cli_rejects_illegal_strategy(tmp_path):
    """Nonzero exit + rule-tagged diagnostic from the process itself.
    What the process's wall time holds (interpreter, jax import, the
    other xdist workers' load) is not the analyzer's to answer for."""
    r = _run_cli(*_illegal_strategy_files(tmp_path), "--mesh", "data=8")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "legality/indivisible-partition" in r.stdout


def test_cli_rejects_illegal_strategy_fast(tmp_path):
    """The claim the 5 s budget was written for: a verdict without
    tracing or compiling.  The CLI times its analysis in the
    ``analysis/cli`` span and prints the seconds on the verdict line."""
    r = _run_cli(*_illegal_strategy_files(tmp_path), "--mesh", "data=8")
    took = re.search(r"error\(s\).*\[analysis ([0-9.]+) s\]", r.stdout)
    assert took, r.stdout + r.stderr
    assert float(took.group(1)) < 5.0, f"verdict took {took.group(1)} s"


def test_cli_linear_regression_example_clean():
    """The shapes of examples/linear_regression.py under its default
    builder (PSLoadBalancing) analyze clean on the virtual 8-chip mesh."""
    r = _run_cli("linear_regression", "PSLoadBalancing", "--mesh", "data=8")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 error(s)" in r.stdout


def test_cli_pipeline_example_clean():
    """The stage-stacked pipeline example shapes analyze clean on a
    pipe=4 × data=2 mesh (the examples/pipeline_1f1b.py layout)."""
    r = _run_cli("pipeline", "AllReduce", "--mesh", "pipe=4,data=2")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 error(s)" in r.stdout


def test_cli_every_builder_on_every_demo_model():
    """Shipped builders × builtin demo catalogs: all clean (one process,
    importing the CLI in-proc to keep the matrix fast)."""
    from autodist_tpu.analysis.__main__ import main

    for model in ("linear_regression", "mlp", "embedding_lm", "moe"):
        for builder in ("AllReduce", "PS", "PSLoadBalancing",
                        "PartitionedPS", "Parallax", "AutoStrategy"):
            rc = main([model, builder, "--mesh", "data=8"])
            assert rc == 0, (model, builder)


def test_cli_json_output_and_budget(tmp_path):
    from autodist_tpu.analysis.__main__ import main
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = main(["mlp", "AllReduce", "--mesh", "data=8", "--json",
                   "--budget-gb", "0.000001"])
    out = json.loads(buf.getvalue())
    assert rc == 1
    assert any(d["rule"] == "memory/watermark-exceeds-hbm"
               for d in out["diagnostics"])


def test_cli_list_rules_runs():
    from autodist_tpu.analysis.__main__ import main
    assert main(["--list-rules"]) == 0
