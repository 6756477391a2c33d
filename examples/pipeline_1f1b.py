"""Pipelined LM trained through the 1F1B schedule (hand-built backward).

Two specs of the same model train side by side: GPipe (autodiff through
the tick-scan — O(M) stashed activations) and 1F1B
(``parallel/pipeline_1f1b.py`` — backward interleaved into the ring,
O(S·V) stashed activations, plugged in via ``capture(grad_fn=...)``).
Their losses match step for step; the memory difference is what you buy.
``--virtual-stages V`` selects the interleaved layout for BOTH schedules
(each device holds V chunks; the warmup/drain bubble shrinks — see the
algebra in ``parallel/pipeline_1f1b.py``).

Run (CPU mesh rehearsal):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/pipeline_1f1b.py --virtual-stages 2 --num-layers 8
(num_layers must divide into pipe x virtual-stages chunks.)
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import optax


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--pipe", type=int, default=4)
    p.add_argument("--virtual-stages", type=int, default=1)
    p.add_argument("--num-layers", type=int, default=4)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--steps", type=int, default=5)
    args = p.parse_args()

    from autodist_tpu import AutoDist
    from autodist_tpu.autodist import _reset_default_autodist_for_testing
    from autodist_tpu.mesh import build_mesh
    from autodist_tpu.models.pipelined_lm import pipelined_transformer_lm
    from autodist_tpu.strategy import PSLoadBalancing

    axes = {"pipe": args.pipe, "data": 2}
    mesh = build_mesh(axes)
    if args.num_layers % (args.pipe * args.virtual_stages):
        p.error("--num-layers must divide into pipe x virtual-stages "
                "chunks")
    kw = dict(vocab_size=2048, num_layers=args.num_layers, num_heads=4,
              head_dim=16, d_ff=64, max_len=args.seq_len,
              seq_len=args.seq_len,
              num_virtual_stages=args.virtual_stages)

    losses = {}
    for sched in ("1f1b", "gpipe"):
        # DEMO-ONLY: a real training script builds ONE AutoDist per
        # process (the reference's rule).  This side-by-side comparison
        # needs two, so it uses the testing reset (requires
        # AUTODIST_IS_TESTING=True, like the test matrices do).
        os.environ.setdefault("AUTODIST_IS_TESTING", "True")
        _reset_default_autodist_for_testing()
        spec = pipelined_transformer_lm(mesh, schedule=sched, **kw)
        params = spec.init(jax.random.PRNGKey(0))
        ad = AutoDist(strategy_builder=PSLoadBalancing(), mesh_axes=axes)
        with ad.scope():
            ad.capture(params=params, optimizer=optax.adam(1e-2),
                       loss_fn=spec.loss_fn, grad_fn=spec.grad_fn,
                       sparse_vars=spec.sparse_vars,
                       pipeline_vars=spec.pipeline_vars)
        sess = ad.create_distributed_session(mesh=mesh)
        batch = spec.sample_batch(args.batch_size)
        losses[sched] = [float(sess.run(batch)["loss"])
                         for _ in range(args.steps)]
        print(f"{sched:>6}: " + " ".join(f"{v:.4f}" for v in losses[sched]))

    drift = max(abs(a - b) / abs(a)
                for a, b in zip(losses["1f1b"], losses["gpipe"]))
    print(f"max relative drift 1F1B vs GPipe: {drift:.2e}")
    assert drift < 1e-3


if __name__ == "__main__":
    main()
