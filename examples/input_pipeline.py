"""End-to-end input pipeline: native C++ loader → fit → async checkpoints.

Parity target: the reference fed training through feed_dict remapping
(``autodist/remapper.py:81-123``) with no input pipeline of its own.  Here
the full TPU-era loop: the native prefetching ``DataLoader`` (C++ threads
gather + bf16-cast batches on host) feeds ``session.fit`` (device
prefetch + async dispatch), while an ``async_save`` Saver persists
checkpoints in the background of training.

Run (CPU mesh rehearsal):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/input_pipeline.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax.numpy as jnp
import numpy as np
import optax


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--rows", type=int, default=4096)
    p.add_argument("--checkpoint-dir", default="/tmp/autodist_tpu_pipeline")
    args = p.parse_args()

    from autodist_tpu import AutoDist, TimeHistory
    from autodist_tpu.runtime.data_loader import DataLoader
    from autodist_tpu.strategy import PSLoadBalancing

    # Synthetic regression dataset, float32 on host; the loader casts the
    # features to bf16 while gathering (C++ threads, not the TPU's time).
    rng = np.random.RandomState(0)
    x = rng.randn(args.rows, 64).astype(np.float32)
    w = rng.randn(64, 8).astype(np.float32)
    y = (x @ w).astype(np.float32)
    loader = DataLoader({"x": x, "y": y}, batch_size=args.batch_size,
                        shuffle=True, to_bf16=["x"], num_threads=4,
                        prefetch_depth=2)

    params = {"w": jnp.zeros((64, 8)), "b": jnp.zeros((8,))}

    def loss_fn(p, batch):
        pred = batch["x"].astype(jnp.float32) @ p["w"] + p["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    ad = AutoDist(strategy_builder=PSLoadBalancing())
    with ad.scope():
        ad.capture(params=params, optimizer=optax.adam(0.05),
                   loss_fn=loss_fn)
    sess = ad.create_distributed_session()

    th = TimeHistory(items_per_step=args.batch_size)
    hist = sess.fit(loader, epochs=args.epochs, callbacks=[th],
                    log_every=20, checkpoint_dir=args.checkpoint_dir,
                    async_checkpoints=True)
    for e, rate in enumerate(th.items_per_sec):
        print(f"epoch {e}: {rate:,.0f} samples/sec, "
              f"loss {hist.history['epoch_loss'][e]:.5f}")
    print(f"final loss {hist.history['epoch_loss'][-1]:.6f} after "
          f"{hist.steps_run} steps; checkpoints in {args.checkpoint_dir}")


if __name__ == "__main__":
    main()
