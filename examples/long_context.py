"""Long-context training showcase: ring attention + flash attention.

New scope beyond the reference (it has no sequence-scaling machinery,
SURVEY §5.7): a causal LM whose sequence dimension is sharded over the
``seq`` mesh axis — K/V blocks rotate between chips via ppermute (ring
attention) so max context grows linearly with chips at constant per-chip
memory — while per-chip attention blocks use the Pallas flash kernel.

Run (CPU mesh rehearsal):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/long_context.py --seq-len 512
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import optax

from autodist_tpu.mesh import build_mesh
from autodist_tpu.models.transformer_lm import transformer_lm
from autodist_tpu.parallel import make_ring_attention
from examples.benchmark.common import benchmark_args, make_autodist, \
    run_selected_benchmark


def main():
    p = benchmark_args("long-context LM (sequence parallelism)")
    p.set_defaults(strategy="PartitionedPS", batch_size=4)
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--seq-shards", type=int, default=4)
    p.add_argument("--data-shards", type=int, default=2)
    args = p.parse_args()

    axes = {"data": args.data_shards, "seq": args.seq_shards}
    mesh = build_mesh(axes)
    spec = transformer_lm(
        vocab_size=2048, num_layers=2, num_heads=4, head_dim=32, d_ff=512,
        max_len=args.seq_len, seq_len=args.seq_len,
        attn_fn=make_ring_attention(mesh))
    params = spec.init(jax.random.PRNGKey(0))

    ad = make_autodist(args, mesh_axes=axes)
    with ad.scope():
        ad.capture(params=params, optimizer=optax.adamw(args.lr),
                   loss_fn=spec.loss_fn, sparse_vars=spec.sparse_vars)
    sess = ad.create_distributed_session(mesh=mesh)
    run_selected_benchmark(
        spec, sess, args, unit="tokens",
        items_per_batch=args.batch_size * args.seq_len)


if __name__ == "__main__":
    main()
