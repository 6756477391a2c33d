"""Linear regression — the minimal end-to-end example.

Parity target: reference ``examples/linear_regression.py`` (TF1 graph built
under ``ad.scope()``, trained via ``ad.create_distributed_session()``).
TPU-native version: capture a functional program, run distributed steps.

Run (CPU mesh rehearsal):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/linear_regression.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from autodist_tpu import AutoDist
from autodist_tpu.strategy import PSLoadBalancing

TRUE_W, TRUE_B = 3.0, 2.0
NUM_EXAMPLES = 2000
LR = 0.01
STEPS = 200


def main():
    rng = np.random.RandomState(42)
    inputs = rng.randn(NUM_EXAMPLES).astype(np.float32)
    noises = rng.randn(NUM_EXAMPLES).astype(np.float32)
    outputs = inputs * TRUE_W + TRUE_B + noises * 0.1

    params = {"w": jnp.array(5.0), "b": jnp.array(0.0)}

    def loss_fn(params, batch):
        pred = params["w"] * batch["x"] + params["b"]
        return jnp.mean((batch["y"] - pred) ** 2)

    ad = AutoDist(strategy_builder=PSLoadBalancing())
    with ad.scope():
        ad.capture(params=params, optimizer=optax.sgd(LR), loss_fn=loss_fn)
    sess = ad.create_distributed_session()

    batch = {"x": inputs, "y": outputs}
    for step in range(STEPS):
        metrics = sess.run(batch)
        if step % 50 == 0:
            print(f"step {step:4d} loss {float(metrics['loss']):.5f}")

    final = sess.params
    print(f"learned w={float(final['w']):.3f} (true {TRUE_W}), "
          f"b={float(final['b']):.3f} (true {TRUE_B})")
    assert abs(float(final["w"]) - TRUE_W) < 0.1
    assert abs(float(final["b"]) - TRUE_B) < 0.1
    print("OK")


if __name__ == "__main__":
    main()
