"""Sentiment classifier — reference ``examples/sentiment_classifier.py``
parity: embedding → mean-pool → 2-layer MLP → binary cross entropy,
trained under PartitionedPS (the vocab-sized embedding is what the
variable partitioner is for).  Synthetic separable data stands in for
IMDB, like the reference's random batches.

Run (CPU mesh rehearsal):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/sentiment_classifier.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=300)
    p.add_argument("--batch-size", type=int, default=128)
    args = p.parse_args()
    if args.steps < 1:
        p.error("--steps must be >= 1")

    from autodist_tpu import AutoDist
    from autodist_tpu.strategy import PartitionedPS

    vocab, emb_dim, hidden, seq = 10000, 16, 16, 20
    rng = np.random.RandomState(0)
    params = {
        "emb": jnp.asarray(rng.rand(vocab, emb_dim), jnp.float32),
        "w1": jnp.asarray(rng.rand(emb_dim, hidden) * 0.1, jnp.float32),
        "b1": jnp.zeros((hidden,), jnp.float32),
        "w2": jnp.asarray(rng.rand(hidden, 1) * 0.1, jnp.float32),
        "b2": jnp.zeros((1,), jnp.float32),
    }
    # Planted signal: each token leans +1/-1; a document's label is the
    # sign of its mean leaning.  Borderline documents (|mean| small) are
    # resampled away so the task is cleanly separable — the reference's
    # synthetic stand-in for IMDB polarity.
    w_tok = np.where(rng.rand(vocab) < 0.5, -1.0, 1.0).astype(np.float32)

    def make_batch(n):
        rows = []
        while len(rows) < n:
            x = rng.randint(0, vocab, (4 * n, seq)).astype(np.int32)
            score = w_tok[x].mean(axis=1)
            keep = np.abs(score) >= 0.3
            rows.extend(zip(x[keep], (score[keep] > 0)))
        x = np.stack([r[0] for r in rows[:n]])
        y = np.array([r[1] for r in rows[:n]], np.float32)
        return {"x": x, "y": y}

    def loss_fn(p, batch):
        h = jnp.take(p["emb"], batch["x"], axis=0).mean(axis=1)
        h = jax.nn.relu(h @ p["w1"] + p["b1"])
        logits = (h @ p["w2"] + p["b2"])[:, 0]
        y = batch["y"]
        return jnp.mean(jnp.maximum(logits, 0) - logits * y
                        + jnp.log1p(jnp.exp(-jnp.abs(logits))))

    ad = AutoDist(strategy_builder=PartitionedPS())
    with ad.scope():
        ad.capture(params=params, optimizer=optax.adam(1e-2),
                   loss_fn=loss_fn, sparse_vars=("emb",))
    sess = ad.create_distributed_session()
    for step in range(args.steps):
        out = sess.run(make_batch(args.batch_size))
        if step % 20 == 0:
            print(f"step {step:3d} loss {float(out['loss']):.4f}")
    final = float(out["loss"])
    print(f"final loss {final:.4f}")
    assert final < 0.45, final   # well below chance (~0.69)


if __name__ == "__main__":
    main()
