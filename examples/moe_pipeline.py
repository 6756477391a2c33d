"""Pipelined MoE LM showcase: pipeline × expert × data parallelism.

New scope beyond the reference (SURVEY §2.8: PP and EP absent): the
stage-stacked MoE transformer — layer stack sharded over ``pipe``
(microbatch ppermute ring), expert weights over ``expert`` (GSPMD
all-to-all dispatch), batch over ``data``.

Run (CPU mesh rehearsal):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/moe_pipeline.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import optax

from autodist_tpu.mesh import build_mesh
from autodist_tpu.models.pipelined_moe_lm import pipelined_moe_transformer_lm
from examples.benchmark.common import benchmark_args, make_autodist, \
    run_selected_benchmark


def main():
    p = benchmark_args("pipelined MoE LM (pp x ep x dp)")
    p.set_defaults(strategy="PSLoadBalancing", batch_size=8)
    p.add_argument("--pipe", type=int, default=2)
    p.add_argument("--experts", type=int, default=4)
    p.add_argument("--num-layers", type=int, default=4)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--remat", action="store_true",
                   help="rematerialize stage internals in backward "
                        "(cuts stashed activation memory)")
    args = p.parse_args()

    axes = {"pipe": args.pipe, "expert": 2, "data": 2}
    mesh = build_mesh(axes)
    spec = pipelined_moe_transformer_lm(
        mesh, vocab_size=2048, num_layers=args.num_layers, num_heads=4,
        head_dim=32, d_ff=512, num_experts=args.experts,
        max_len=args.seq_len, seq_len=args.seq_len, remat=args.remat)
    params = spec.init(jax.random.PRNGKey(0))

    ad = make_autodist(args, mesh_axes=axes)
    with ad.scope():
        ad.capture(params=params, optimizer=optax.adamw(args.lr),
                   loss_fn=spec.loss_fn, sparse_vars=spec.sparse_vars,
                   pipeline_vars=spec.pipeline_vars,
                   expert_vars=spec.expert_vars)
    sess = ad.create_distributed_session(mesh=mesh)
    run_selected_benchmark(
        spec, sess, args, unit="tokens",
        items_per_batch=args.batch_size * args.seq_len)


if __name__ == "__main__":
    main()
