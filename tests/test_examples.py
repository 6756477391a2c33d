"""Example-suite smoke tests: the runnable examples must not rot.

Parity target: the reference's examples ARE its integration workloads
(``tests/integration/cases`` wrap them).  Each example runs as a
subprocess on the virtual CPU mesh: a preamble selects the CPU platform
and 8 devices before the example imports jax (as tests/conftest.py
does)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_STEER = (
    "import os; os.environ['JAX_PLATFORMS']='cpu'; "
    "import jax; jax.config.update('jax_platforms','cpu'); "
    "jax.config.update('jax_num_cpu_devices', 8); "
    "import runpy, sys; sys.argv=[sys.argv[1]]+sys.argv[2:]; "
    "runpy.run_path(sys.argv[0], run_name='__main__')"
)


def _run_example(path, args=(), timeout=420):
    env = dict(os.environ)
    env.update({"AUTODIST_IS_TESTING": "True",
                "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", "")})
    proc = subprocess.run(
        [sys.executable, "-c", _STEER, os.path.join(REPO, path), *args],
        env=env, timeout=timeout, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT)
    out = proc.stdout.decode()
    assert proc.returncode == 0, f"{path} failed:\n{out[-3000:]}"
    return out


def test_linear_regression():
    out = _run_example("examples/linear_regression.py")
    assert "w=" in out or "loss" in out.lower()


def test_implicit_capture():
    out = _run_example("examples/implicit_capture.py")
    assert "step  35" in out


@pytest.mark.integration
def test_long_context():
    _run_example("examples/long_context.py",
                 ("--steps", "2", "--warmup", "1"))


@pytest.mark.integration
def test_moe_pipeline():
    _run_example("examples/moe_pipeline.py",
                 ("--steps", "2", "--warmup", "1"))


@pytest.mark.integration
def test_imagenet_benchmark():
    _run_example("examples/benchmark/imagenet.py",
                 ("--model", "resnet50", "--image-size", "32",
                  "--batch-size", "8", "--steps", "2", "--warmup", "1"))


def test_input_pipeline(tmp_path):
    out = _run_example("examples/input_pipeline.py",
                       ("--epochs", "2", "--rows", "512",
                        "--batch-size", "32",
                        "--checkpoint-dir", str(tmp_path / "ck")))
    assert "final loss" in out
    assert (tmp_path / "ck").is_dir()


@pytest.mark.integration
def test_imagenet_benchmark_fit_epochs():
    out = _run_example("examples/benchmark/imagenet.py",
                       ("--model", "resnet50", "--image-size", "32",
                        "--batch-size", "8", "--steps", "2",
                        "--epochs", "2"))
    assert "epoch 1:" in out


def test_image_classifier():
    out = _run_example("examples/image_classifier.py",
                       ("--image-size", "32", "--batch-size", "8",
                        "--steps", "3"))
    assert "step 2: loss" in out


@pytest.mark.integration
def test_pipeline_1f1b_example():
    out = _run_example("examples/pipeline_1f1b.py",
                       ("--num-layers", "4", "--seq-len", "16",
                        "--batch-size", "8", "--steps", "3"))
    assert "max relative drift" in out


@pytest.mark.integration
def test_lm1b_train_example():
    # The Parallax parity workload at toy sizes (793k-vocab default
    # shrunk); exercises the chunked-xent default loss end-to-end.
    out = _run_example("examples/lm1b/lm1b_train.py",
                       ("--vocab-size", "512", "--emb-dim", "16",
                        "--hidden-dim", "32", "--batch-size", "8",
                        "--steps", "5", "--warmup", "1"))
    assert "words" in out


@pytest.mark.integration
def test_sentiment_classifier_example():
    # Reference examples/sentiment_classifier.py parity; the example
    # asserts its own convergence bar (final loss < 0.45 vs ~0.69 chance).
    out = _run_example("examples/sentiment_classifier.py",
                       ("--steps", "300"))
    assert "final loss" in out


@pytest.mark.integration
def test_generate_text_example():
    # The example enforces its own accuracy bar (assert acc > 0.9);
    # a zero returncode from _run_example is the pass criterion here.
    out = _run_example("examples/generate_text.py", ("--steps", "200"))
    assert "continuation accuracy:" in out


def test_serving_engine_example():
    # The example asserts oracle-exactness of spot-checked results
    # itself; the output lines are the smoke signal.
    out = _run_example("examples/serving_engine.py")
    assert "oracle-exact" in out
    assert "slot_utilization=" in out


def test_lora_finetune_example():
    # The example asserts adapter learning and zero base drift itself.
    out = _run_example("examples/lora_finetune.py", ("--steps", "30"))
    assert "lora_finetune demo OK" in out
    assert "base drift: 0.0" in out


def test_serve_http_example():
    # The example is its own HTTP client (concurrent completions + one
    # SSE stream + stats) and asserts 200s internally.
    out = _run_example("examples/serve_http.py")
    assert "serve_http demo OK" in out
    assert "stream:" in out


@pytest.mark.integration
def test_speculative_draft_example():
    # Trains a target (framework session) and a ~30x-smaller draft,
    # then decodes speculatively; the example asserts acceptance > 0.5
    # and token-exactness vs target greedy itself.
    out = _run_example("examples/speculative_draft.py", timeout=900)
    assert "acceptance rate:" in out
    assert "token-exact" in out


@pytest.mark.integration
def test_pipeline_1f1b_example_interleaved():
    out = _run_example("examples/pipeline_1f1b.py",
                       ("--virtual-stages", "2", "--num-layers", "8",
                        "--seq-len", "16", "--batch-size", "8",
                        "--steps", "3"))
    assert "max relative drift" in out
