"""Live wedge drill (supervisor + chief + worker, peer-tier recovery).

Same three-role layout as ``recovery_drill.py`` (supervisor via
``AUTODIST_SUPERVISE=1``, chief/worker via the real Coordinator over
``jax.distributed``, recovery on the RAM/peer checkpoint tiers — no
persistent checkpoint dir), but the injected fault is a chaos ``hang``:
the worker process blocks INSIDE the step while its heartbeat daemon
keeps beating — the WEDGED-in-a-collective signature only the
monitor's ``step_timeout`` can catch.  Before blocking, the chaos event
stamps a flight-recorder cursor for a REAL leg id of the session's
schedule IR (the ``leg=PLANT`` placeholder in ``AUTODIST_CHAOS`` is
resolved against the IR here and recorded in
``$AUTODIST_TEST_PLANTED``), so the supervisor's verdict must localize
the wedge to the planted leg and the culprit process, write a crash
bundle, and — after the relaunch — ``fit(resume=True)`` must come back
from the peer tier bit-exact with the uninterrupted oracle
(``tests/test_flightrec.py::test_live_hang_drill``)."""
import json
import os
import re
import socket
import subprocess
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                os.environ.get("XLA_FLAGS", "")).strip()
os.environ["XLA_FLAGS"] = \
    (_flags + " --xla_force_host_platform_device_count=2").strip()
os.environ.setdefault("JAX_CPU_COLLECTIVES_IMPLEMENTATION", "gloo")

sys.path.insert(0, os.environ.get("AUTODIST_REPO_ROOT",
                                  os.path.dirname(os.path.dirname(
                                      os.path.dirname(
                                          os.path.abspath(__file__))))))

EPOCHS = 4
SNAPSHOT_EVERY = 2
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
        # The wedge is invisible to beacon age (the daemon keeps
        # beating) — step_timeout is the detector under drill.
        heartbeat_timeout=120.0,
        step_timeout=8.0,
        poll_interval=0.25)
    sup = Supervisor(policy, hosts=["127.0.0.1", "localhost"],
                     workdir=os.environ["AUTODIST_TEST_PEER"] + ".sup")

    def launch(att):
        env = dict(os.environ)
        env.pop("AUTODIST_SUPERVISE", None)
        env.update(att.env())
        env["AUTODIST_COORDINATOR_ADDRESS"] = f"127.0.0.1:{_free_port()}"
        proc = subprocess.Popen([sys.executable, "-u",
                                 os.path.abspath(__file__)],
                                env=env, start_new_session=True)
        return {"chief": proc}

    report = sup.run(launch)
    with open(os.environ["AUTODIST_SUPERVISOR_REPORT"], "w",
              encoding="utf-8") as f:
        json.dump({"ok": report.ok, "attempts": report.attempts,
                   "preemptions": report.preemptions,
                   "gave_up": report.gave_up,
                   "failures": [{"attempt": x.attempt, "kind": x.kind,
                                 "culprit": x.culprit, "detail": x.detail,
                                 "bundle": x.bundle}
                                for x in report.failures]}, f)
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

    # Resolve the chaos PLANT placeholder against the REAL schedule IR
    # (deterministic: every process builds the identical IR) BEFORE the
    # monkey parses the spec — the wedge drill plants a leg id the hang
    # localizer can find in the published schedule.
    chaos_spec = os.environ.get("AUTODIST_CHAOS", "")
    if "leg=PLANT" in chaos_spec:
        ir = sess.schedule_ir
        leg = next(l.id for l in ir.legs
                   if l.kind in ("all_reduce", "reduce_scatter",
                                 "ppermute_hop"))
        os.environ["AUTODIST_CHAOS"] = chaos_spec.replace(
            "leg=PLANT", "leg=" + leg)
        planted = os.environ.get("AUTODIST_TEST_PLANTED")
        if planted:
            with open(planted, "w", encoding="utf-8") as f:
                json.dump({"leg": leg, "fingerprint": ir.fingerprint()},
                          f)

    loader = DataLoader({"x": x, "y": y}, batch_size=8, shuffle=True,
                        seed=7)
    monkey = ChaosMonkey.from_env()
    callbacks = [ChaosCallback(monkey)]
    sup_dir = ENV.AUTODIST_SUPERVISOR_DIR.val
    if sup_dir:
        writer = HeartbeatWriter(
            os.path.join(sup_dir, "hb"),
            f"proc{ENV.AUTODIST_PROCESS_ID.val}", interval=0.5,
            chaos=monkey)
        callbacks.append(HeartbeatCallback(writer))

    # Peer-tier recovery only (env AUTODIST_SNAPSHOT_EVERY/_DIR): the
    # relaunched attempt resumes from the survivor's mirror.
    hist = sess.fit(loader, epochs=EPOCHS, resume=True,
                    callbacks=callbacks)

    result = {
        "role": "worker" if ENV.AUTODIST_WORKER.val else "chief",
        "attempt": ENV.AUTODIST_ATTEMPT.val,
        "process_index": jax.process_index(),
        "final_step": sess.step_count,
        "steps_run_this_attempt": hist.steps_run,
        "resume_tier": hist.resume_tier,
        "final_w": np.asarray(sess.params["w"]).tolist(),
        "final_b": float(np.asarray(sess.params["b"])),
    }
    out = os.environ["AUTODIST_RESULT_FILE"]
    if ENV.AUTODIST_WORKER.val:
        out += ".worker"
    with open(out, "w", encoding="utf-8") as f:
        json.dump(result, f)
    print(f"[{result['role']}] done: step={sess.step_count} "
          f"(resumed via {hist.resume_tier})", flush=True)

    jax.distributed.shutdown()
    if ad.coordinator is not None:
        ad.coordinator.join()


if __name__ == "__main__":
    os.environ.setdefault("AUTODIST_SNAPSHOT_EVERY", str(SNAPSHOT_EVERY))
    os.environ.setdefault("AUTODIST_SNAPSHOT_DIR",
                          os.environ["AUTODIST_TEST_PEER"])
    if os.environ.get("AUTODIST_SUPERVISE"):
        sys.exit(supervise())
    train()
