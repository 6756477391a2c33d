"""lm1b LSTM language-model training (synthetic data), words/sec.

Parity target: reference ``examples/lm1b/lm1b_train.py`` — the 793k-vocab
LSTM LM whose embedding/softmax variables are the reference's flagship
sparse-gradient / PartitionedPS workload (SURVEY §5.7).  The Parallax
strategy reproduces its hybrid: dense grads allreduced, embedding grads
sharded onto the owning vocab shard.

Run (CPU mesh rehearsal, tiny vocab):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/lm1b/lm1b_train.py --vocab-size 4096 --batch-size 16
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import optax

from autodist_tpu.models.lm1b import lm1b
from examples.benchmark.common import benchmark_args, make_autodist, \
    run_selected_benchmark


def main():
    p = benchmark_args("lm1b LSTM LM benchmark")
    p.set_defaults(strategy="Parallax")
    p.add_argument("--vocab-size", type=int, default=793472)
    p.add_argument("--seq-len", type=int, default=20)
    p.add_argument("--emb-dim", type=int, default=512)
    p.add_argument("--hidden-dim", type=int, default=2048)
    args = p.parse_args()

    spec = lm1b(vocab_size=args.vocab_size, seq_len=args.seq_len,
                emb_dim=args.emb_dim, hidden_dim=args.hidden_dim)
    params = spec.init(jax.random.PRNGKey(0))

    ad = make_autodist(args)
    with ad.scope():
        ad.capture(params=params, optimizer=optax.adagrad(args.lr),
                   loss_fn=spec.loss_fn, sparse_vars=spec.sparse_vars)
    sess = ad.create_distributed_session()
    run_selected_benchmark(
        spec, sess, args, unit="words",
        items_per_batch=args.batch_size * args.seq_len)


if __name__ == "__main__":
    main()
