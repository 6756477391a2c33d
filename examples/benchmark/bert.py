"""BERT pre-training benchmark (synthetic MLM data).

Parity target: reference ``examples/benchmark/bert.py`` (BERT-large
uncased pre-training, samples/sec).

Run (CPU mesh rehearsal, tiny):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/benchmark/bert.py --size tiny --batch-size 8
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import optax

from autodist_tpu.models.bert import bert, bert_base, bert_large
from examples.benchmark.common import benchmark_args, make_autodist, \
    run_selected_benchmark

SIZES = {
    "tiny": lambda **kw: bert(num_layers=2, num_heads=2, head_dim=32,
                              d_ff=256, vocab_size=1024, **kw),
    "base": bert_base,
    "large": bert_large,
}


def main():
    p = benchmark_args("BERT pre-training benchmark")
    p.add_argument("--size", default="base", choices=sorted(SIZES))
    p.add_argument("--seq-len", type=int, default=128)
    args = p.parse_args()

    spec = SIZES[args.size](seq_len=args.seq_len)
    params = spec.init(jax.random.PRNGKey(0))

    ad = make_autodist(args)
    with ad.scope():
        ad.capture(params=params,
                   optimizer=optax.adamw(args.lr),
                   loss_fn=spec.loss_fn, sparse_vars=spec.sparse_vars)
    sess = ad.create_distributed_session()
    run_selected_benchmark(spec, sess, args, unit="samples")


if __name__ == "__main__":
    main()
