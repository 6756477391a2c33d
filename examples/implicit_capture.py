"""Zero-code-change adoption: a plain optax training script distributed by
wrapping it in ``ad.scope()`` — no ``capture()`` call, no session plumbing
in the model code (the reference's ``PatchTensorFlow`` promise,
``autodist/patch.py:40-116``; here via ``autodist_tpu/patch.py``).

Run (CPU mesh rehearsal):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/implicit_capture.py

or through the launcher with a cluster spec:

    python -m autodist_tpu.run -r pod.yml examples/implicit_capture.py
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax

from autodist_tpu import AutoDist


def loss_fn(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


def main():
    params = {"w": jnp.zeros((8, 4)), "b": jnp.zeros(4)}

    ad = AutoDist()  # spec auto-derived (or from the launcher env)
    with ad.scope():
        # ---- an ordinary single-device optax script prefix ----
        optimizer = optax.chain(optax.clip_by_global_norm(10.0),
                                optax.adamw(5e-2))
        opt_state = optimizer.init(params)            # params captured
        value_and_grad = jax.value_and_grad(loss_fn)  # loss_fn captured
        # -------------------------------------------------------

    session = ad.create_distributed_session()
    rng = np.random.RandomState(0)
    w_true = rng.randn(8, 4).astype(np.float32)
    for step in range(40):
        x = rng.randn(64, 8).astype(np.float32)
        batch = {"x": x, "y": x @ w_true + 0.1}
        metrics = session.run(batch)
        if step % 5 == 0:
            print(f"step {step:3d}  loss {float(metrics['loss']):.5f}  "
                  f"mesh {dict(session.mesh.shape)}")


if __name__ == "__main__":
    main()
