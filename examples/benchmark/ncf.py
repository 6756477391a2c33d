"""NCF (neural collaborative filtering) benchmark, samples/sec.

Parity target: reference ``examples/benchmark`` NCF on MovieLens.  The
user/item embedding tables are the sparse-gradient variables; PS-family
strategies shard them across the mesh.

Run (CPU mesh rehearsal, tiny):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/benchmark/ncf.py --num-users 1024 --num-items 512
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import optax

from autodist_tpu.models.ncf import ncf
from examples.benchmark.common import benchmark_args, make_autodist, \
    run_selected_benchmark


def main():
    p = benchmark_args("NCF benchmark")
    p.set_defaults(strategy="PSLoadBalancing", batch_size=256)
    p.add_argument("--num-users", type=int, default=138496)
    p.add_argument("--num-items", type=int, default=26752)
    args = p.parse_args()

    spec = ncf(num_users=args.num_users, num_items=args.num_items)
    params = spec.init(jax.random.PRNGKey(0))

    ad = make_autodist(args)
    with ad.scope():
        ad.capture(params=params, optimizer=optax.adam(args.lr),
                   loss_fn=spec.loss_fn, sparse_vars=spec.sparse_vars)
    sess = ad.create_distributed_session()
    run_selected_benchmark(spec, sess, args, unit="samples")


if __name__ == "__main__":
    main()
