"""ImageNet-family training benchmark (synthetic data).

Parity target: reference ``examples/benchmark/imagenet.py`` — ResNet101 /
DenseNet121 / InceptionV3 / VGG16 via keras.applications with a chosen
AutoDist strategy, reporting images/sec.  Same families here (plus
ResNet-50, the BASELINE.md headline model) from the TPU-first model zoo.

Run (CPU mesh rehearsal):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/benchmark/imagenet.py --model resnet50 \
        --image-size 64 --batch-size 16
On a machine with a TPU: python examples/benchmark/imagenet.py --model
resnet50 (no JAX_PLATFORMS, no XLA_FLAGS; its images/sec then is the chip's).
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import optax

from autodist_tpu import models
from examples.benchmark.common import benchmark_args, make_autodist, \
    run_selected_benchmark


def main():
    p = benchmark_args("ImageNet model-family benchmark")
    p.add_argument("--model", default="resnet50",
                   choices=["resnet50", "resnet101", "vgg16", "densenet121",
                            "inception_v3"])
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    args = p.parse_args()

    spec = models.ALL_MODELS[args.model](num_classes=args.num_classes,
                                         image_size=args.image_size)
    params = spec.init(__import__("jax").random.PRNGKey(0))

    ad = make_autodist(args)
    with ad.scope():
        ad.capture(params=params,
                   optimizer=optax.sgd(args.lr, momentum=0.9),
                   loss_fn=spec.loss_fn,
                   untrainable_vars=spec.untrainable_vars)
    sess = ad.create_distributed_session()
    run_selected_benchmark(spec, sess, args, unit="images")


if __name__ == "__main__":
    main()
