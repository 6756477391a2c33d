"""Minimal image-classifier training (reference ``examples/image_classifier.py``).

The reference's simplest end-to-end GPU script: ResNet-50 under an
AutoDist scope with a fixed strategy, a few training steps.  Same shape
here on the TPU mesh (BASELINE.json parity config: "ResNet-50 —
AllReduce").  For the measured benchmark loop use
``benchmark/imagenet.py``.

Run (CPU mesh rehearsal):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/image_classifier.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np
import optax


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--image-size", type=int, default=64)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--steps", type=int, default=5)
    args = p.parse_args()
    if args.steps < 1:
        p.error("--steps must be >= 1")

    from autodist_tpu import AutoDist
    from autodist_tpu.models.resnet import resnet50
    from autodist_tpu.strategy import AllReduce

    spec = resnet50(num_classes=100, image_size=args.image_size)
    params = spec.init(jax.random.PRNGKey(0))

    ad = AutoDist(strategy_builder=AllReduce())
    with ad.scope():
        ad.capture(params=params, optimizer=optax.sgd(0.1, momentum=0.9),
                   loss_fn=spec.loss_fn)
    sess = ad.create_distributed_session()

    rng = np.random.RandomState(0)
    for step in range(args.steps):
        metrics = sess.run(spec.make_batch(rng, args.batch_size))
        print(f"step {step}: loss {float(metrics['loss']):.4f}")
    assert np.isfinite(float(metrics["loss"]))


if __name__ == "__main__":
    main()
