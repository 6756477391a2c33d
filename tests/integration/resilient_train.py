"""Supervised-recovery integration script (chief + worker + supervisor).

Three roles, selected by env:

* ``AUTODIST_SUPERVISE=1`` — SUPERVISOR: builds a
  :class:`~autodist_tpu.resilience.Supervisor` and launches this same
  script (train role) as the job's chief, relaunching it with backoff
  when it fails; writes a JSON report to ``$AUTODIST_SUPERVISOR_REPORT``.
* chief (no role env) — TRAIN: 2-node AutoDist job; the real
  Coordinator re-launches the script as the worker (``AUTODIST_WORKER``
  set), both rendezvous via ``jax.distributed``, and ``fit`` trains a
  linear model from a shuffled DataLoader with per-epoch checkpoints,
  exact mid-epoch data state, heartbeats, and the chaos harness.
* worker — same TRAIN code path, launched by the Coordinator.

The chaos spec (``AUTODIST_CHAOS``, e.g. ``kill@step=6,proc=1,attempt=0``)
kills the worker mid-run on the first attempt only; the chief's watcher
fires the ``supervised`` failure policy (marker + exit 73), the
supervisor terminates stragglers, backs off, and relaunches — attempt 1
resumes from the last durable checkpoint and must land on exactly the
same final parameters as an uninterrupted run (the pytest driver,
``tests/test_multiprocess_resilience.py``, asserts this against an
oracle run with chaos disabled).
"""
import json
import os
import re
import socket
import subprocess
import sys

# 2 local CPU devices per process -> 4 global over 2 processes, replacing
# whatever count the parent test process forced through XLA_FLAGS.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                os.environ.get("XLA_FLAGS", "")).strip()
os.environ["XLA_FLAGS"] = \
    (_flags + " --xla_force_host_platform_device_count=2").strip()
# Cross-process CPU collectives.
os.environ.setdefault("JAX_CPU_COLLECTIVES_IMPLEMENTATION", "gloo")

sys.path.insert(0, os.environ.get("AUTODIST_REPO_ROOT",
                                  os.path.dirname(os.path.dirname(
                                      os.path.dirname(
                                          os.path.abspath(__file__))))))

EPOCHS = 4
BATCHES_PER_EPOCH = 4   # 32 rows / batch 8
LR = 0.1


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def supervise() -> int:
    from autodist_tpu.resilience import Backoff, Supervisor, SupervisorPolicy

    policy = SupervisorPolicy(
        max_restarts=int(os.environ.get("AUTODIST_TEST_MAX_RESTARTS", "2")),
        backoff=Backoff(max_tries=8, base=0.2, cap=0.5, jitter=0.5, seed=0),
        # generous: the monitor path runs live, but CPU-test step times
        # must never trip it
        heartbeat_timeout=120.0,
        poll_interval=0.25)
    sup = Supervisor(policy, hosts=["127.0.0.1", "localhost"],
                     checkpoint_dir=os.environ["AUTODIST_TEST_CKPT"],
                     workdir=os.environ["AUTODIST_TEST_CKPT"] + ".sup")

    def launch(att):
        env = dict(os.environ)
        env.pop("AUTODIST_SUPERVISE", None)
        env.update(att.env())
        # fresh rendezvous port per attempt: the previous chief's
        # coordination service socket may still be in TIME_WAIT
        env["AUTODIST_COORDINATOR_ADDRESS"] = f"127.0.0.1:{_free_port()}"
        proc = subprocess.Popen([sys.executable, "-u",
                                 os.path.abspath(__file__)],
                                env=env, start_new_session=True)
        return {"chief": proc}

    report = sup.run(launch)
    with open(os.environ["AUTODIST_SUPERVISOR_REPORT"], "w",
              encoding="utf-8") as f:
        json.dump({
            "ok": report.ok, "attempts": report.attempts,
            "hosts": report.hosts, "gave_up": report.gave_up,
            "failures": [{"attempt": x.attempt, "kind": x.kind,
                          "culprit": x.culprit, "detail": x.detail}
                         for x in report.failures],
        }, f)
    return 0 if report.ok else 1


def train() -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    import numpy as np
    import optax

    from autodist_tpu.autodist import AutoDist
    from autodist_tpu.const import ENV
    from autodist_tpu.resilience import (
        ChaosCallback, ChaosMonkey, HeartbeatCallback, HeartbeatWriter)
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.runtime.data_loader import DataLoader
    from autodist_tpu.strategy import AllReduce

    rng = np.random.RandomState(42)
    x = rng.randn(32, 3).astype(np.float32)
    y = (x @ np.array([1.0, -2.0, 0.5], np.float32) + 0.25).astype(np.float32)
    params = {"w": np.zeros(3, np.float32), "b": np.zeros((), np.float32)}

    def loss_fn(p, batch):
        import jax.numpy as jnp

        pred = batch["x"] @ p["w"] + p["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    pool = []
    for a in ("127.0.0.1", "localhost", socket.gethostname()):
        if a not in pool:
            pool.append(a)
    spec = ResourceSpec(resource_info={
        "nodes": [{"address": pool[i], "chips": 2,
                   **({"chief": True} if i == 0 else {})}
                  for i in range(2)]})

    ad = AutoDist(resource_spec=spec, strategy_builder=AllReduce())
    with ad.scope():
        ad.capture(params=params, optimizer=optax.sgd(LR), loss_fn=loss_fn)
    sess = ad.create_distributed_session()

    # Every process feeds the same shuffled global batches: one loader,
    # one seed, SPMD lockstep — and its state() rides the checkpoints.
    loader = DataLoader({"x": x, "y": y}, batch_size=8, shuffle=True,
                        seed=7)

    monkey = ChaosMonkey.from_env()
    callbacks = [ChaosCallback(monkey)]
    sup_dir = ENV.AUTODIST_SUPERVISOR_DIR.val
    if sup_dir:
        writer = HeartbeatWriter(
            os.path.join(sup_dir, "hb"),
            f"proc{ENV.AUTODIST_PROCESS_ID.val}", interval=1.0,
            chaos=monkey)
        callbacks.append(HeartbeatCallback(writer))

    hist = sess.fit(loader, epochs=EPOCHS,
                    checkpoint_dir=os.environ["AUTODIST_TEST_CKPT"],
                    checkpoint_every=1, resume=True, callbacks=callbacks)

    result = {
        "role": "worker" if ENV.AUTODIST_WORKER.val else "chief",
        "attempt": ENV.AUTODIST_ATTEMPT.val,
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "final_step": sess.step_count,
        "steps_run_this_attempt": hist.steps_run,
        "epoch_loss": hist.history["epoch_loss"],
        "final_w": np.asarray(sess.params["w"]).tolist(),
        "final_b": float(np.asarray(sess.params["b"])),
    }
    out = os.environ["AUTODIST_RESULT_FILE"]
    if ENV.AUTODIST_WORKER.val:
        out += ".worker"
    with open(out, "w", encoding="utf-8") as f:
        json.dump(result, f)
    print(f"[{result['role']}] done: step={sess.step_count}", flush=True)

    # Explicit shutdown BEFORE the chief joins the worker (see
    # dist_train.py: jax's atexit barrier would deadlock the join).
    jax.distributed.shutdown()
    if ad.coordinator is not None:
        ad.coordinator.join()


if __name__ == "__main__":
    if os.environ.get("AUTODIST_SUPERVISE"):
        sys.exit(supervise())
    train()
