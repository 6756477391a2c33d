"""Session throughput/MFU instrumentation (utils/metrics.py).

The reference measured throughput only in example scripts
(``examples/benchmark/imagenet.py:85-120`` TimeHistory); here it is a
DistributedSession feature, plus MFU from XLA cost analysis."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.autodist import AutoDist, _reset_default_autodist_for_testing
from autodist_tpu.strategy import AllReduce
from autodist_tpu.utils import metrics


@pytest.fixture(autouse=True)
def _reset():
    _reset_default_autodist_for_testing()


def _session():
    params = {"w": jnp.zeros((8, 4))}

    def loss(p, b):
        return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)

    ad = AutoDist(strategy_builder=AllReduce())
    with ad.scope():
        ad.capture(params=params, optimizer=optax.sgd(0.1), loss_fn=loss)
    rng = np.random.RandomState(0)
    batch = {"x": rng.randn(16, 8).astype(np.float32),
             "y": rng.randn(16, 4).astype(np.float32)}
    return ad.create_distributed_session(), batch


def test_throughput_meter_window():
    m = metrics.ThroughputMeter(window=4)
    assert m.step_time() is None
    import time

    for _ in range(6):
        m.tick()
        time.sleep(0.001)
    assert m.steps_recorded == 4  # window-bounded
    st = m.step_time()
    assert st is not None and st > 0
    s = m.stats(items_per_step=32)
    assert s["steps_per_sec"] > 0 and s["items_per_sec"] > 0


def test_session_throughput_and_flops():
    sess, batch = _session()
    assert sess.throughput()["step_time_ms"] is None  # no steps yet
    for _ in range(4):
        sess.run(batch)
    t = sess.throughput(items_per_step=16)
    assert t["steps_measured"] == 3
    assert t["step_time_ms"] > 0 and t["items_per_sec"] > 0
    flops = sess.flops_per_step()
    assert flops is None or flops > 0
    assert sess.flops_per_step() is flops  # cached


def test_session_mfu_none_on_cpu():
    sess, batch = _session()
    for _ in range(3):
        sess.run(batch)
    # CPU has no known peak -> None (on TPU this returns a fraction).
    assert sess.mfu() is None


def test_peak_flops_table():
    class FakeDev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    assert metrics.peak_flops_per_chip(FakeDev()) == 197e12
    # 19.7 TFLOP in 1 s on a 197-TFLOP/s chip = 10% MFU.
    assert metrics.mfu(19.7e12, 1.0, [FakeDev()]) == pytest.approx(0.1)
    # two chips halve it
    assert metrics.mfu(19.7e12, 1.0,
                       [FakeDev(), FakeDev()]) == pytest.approx(0.05)

    class UnknownTpu:
        platform = "tpu"
        device_kind = "TPU v5"

    # No substring match: an unlisted TPU kind is an error, not v5e's peak.
    with pytest.raises(ValueError, match="TPU v5"):
        metrics.peak_flops_per_chip(UnknownTpu())
    assert metrics.peak_flops_per_chip(jax.devices()[0]) is None  # cpu
