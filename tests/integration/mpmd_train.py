"""Live MPMD pipeline drill: 2 stages x 2 DP processes over gloo.

The ISSUE 19 acceptance drill.  The PARENT (default mode) orchestrates
two stage GROUPS — each its own ``jax.distributed`` world (own
coordinator port, 2 processes x 1 CPU device, gloo collectives) — whose
only coupling is the shared ``AUTODIST_MPMD_DIR`` activation plane.
Each child process (``AUTODIST_MPMD_ROLE=stage``) runs one
:class:`~autodist_tpu.parallel.mpmd.runner.StageRunner` over THE
verified :func:`~autodist_tpu.parallel.mpmd.partition.build_pipeline_ir`
program, with bucketed ZeRO-1 sync inside the stage group.

Two jobs, three assertions (the pytest driver in tests/test_mpmd.py):

* **parity** — the no-chaos job's per-step losses match the
  single-program ``one_f_one_b`` oracle (same stacked params, pipe=2
  mesh, one process) to <= 1e-5;
* **bit-exact recovery** — the chaos job
  (``kill@step=1,proc=0,attempt=0,stage=1`` fells one worker of stage
  1; the parent supervisor relaunches that WHOLE group on a fresh port
  with ``AUTODIST_ATTEMPT=1``, and the runners restore their per-step
  snapshots) reproduces the no-chaos job's losses and final parameter
  bytes exactly — the restarted group replays the wedged step from the
  transport plane's still-published blobs (recv's non-consuming
  contract);
* **static == runtime** — every child asserts the fingerprint it
  executes equals an independently rebuilt ``ir_from_facts``
  fingerprint, and reports it for cross-process equality.

Result protocol: each child appends one JSON line per completed step to
``$AUTODIST_MPMD_LOG.s<stage>r<rank>`` (losses survive a mid-run kill);
the parent writes the stitched report to ``$AUTODIST_RESULT_FILE``.
"""
import json
import os
import re
import socket
import subprocess
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                os.environ.get("XLA_FLAGS", "")).strip()
# stage workers contribute ONE local device each to their 2-process
# gloo world; the parent needs two for the single-program oracle mesh
_ndev = 1 if os.environ.get("AUTODIST_MPMD_ROLE") == "stage" else 2
os.environ["XLA_FLAGS"] = \
    (_flags + f" --xla_force_host_platform_device_count={_ndev}").strip()
os.environ.setdefault("JAX_CPU_COLLECTIVES_IMPLEMENTATION", "gloo")

sys.path.insert(0, os.environ.get("AUTODIST_REPO_ROOT",
                                  os.path.dirname(os.path.dirname(
                                      os.path.dirname(
                                          os.path.abspath(__file__))))))

S, DP = 2, 2                 # stages x data-parallel ranks per stage
L, D = 4, 8                  # layers, width
M = 4                        # microbatches
B = 16                       # global batch (M x DP x 2 rows)
STEPS = 4
LR = 0.1
KILL_CODE = 43


def _case():
    """The deterministic model + data every process derives locally."""
    import numpy as np

    rng = np.random.RandomState(0)
    layers = [{"w": (rng.randn(D, D) * 0.3).astype(np.float32),
               "b": (rng.randn(D) * 0.1).astype(np.float32)}
              for _ in range(L)]
    x = rng.randn(B, D).astype(np.float32)
    tgt = rng.randn(B, D).astype(np.float32)
    return layers, x, tgt


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# -- child: one stage worker --------------------------------------------------

def stage_worker() -> None:
    stage = int(os.environ["AUTODIST_MPMD_STAGE"])
    rank = int(os.environ["AUTODIST_MPMD_DP_RANK"])
    coord = os.environ["AUTODIST_MPMD_COORD"]

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 1)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    # THIS stage group's own world: stage-local rendezvous, so the
    # pipeline is genuinely MPMD — two programs that never co-issue.
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=DP, process_id=rank)

    import jax.numpy as jnp  # noqa: F401
    import numpy as np

    from autodist_tpu.kernel.synchronization import schedule_ir as sir
    from autodist_tpu.parallel import mpmd

    layers, x, tgt = _case()
    part, stage_params = mpmd.partition_params(layers, S)
    prog = mpmd.build_pipeline_ir(
        layer_params=layers, num_stages=S, num_microbatches=M,
        act_nbytes=(B // (M * DP)) * D * 4, data_axis=DP,
        zero1=True, bucket_bytes=1 << 20)
    # static == runtime: an independent ir_from_facts rebuild must hash
    # to the fingerprint this runner executes.
    rebuilt = sir.ir_from_facts(
        list(prog.facts), axes=dict(prog.axes),
        accum_steps=int(prog.ir.accum_steps), pipeline=list(prog.pipeline))
    assert rebuilt.fingerprint() == prog.ir.fingerprint(), \
        (rebuilt.fingerprint(), prog.ir.fingerprint())

    names = part.param_names(stage)

    def stage_fn(p, h):
        for j in sorted({n.split("/")[1] for n in p},
                        key=lambda s: int(s[1:])):
            pre = f"{sir.stage_name(stage)}/{j}"
            h = jnp.tanh(h @ p[f"{pre}/w"] + p[f"{pre}/b"])
        return h

    def mse(y, t):
        return jnp.mean((y - t) ** 2)

    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()), ("data",))
    transport = mpmd.ActivationTransport(
        os.environ["AUTODIST_MPMD_DIR"], channel=f"dp{rank}")
    runner = mpmd.StageRunner(
        prog, stage, stage_fn=stage_fn, params=stage_params[stage],
        transport=transport, lr=LR,
        loss_fn=mse if stage == S - 1 else None,
        mesh=mesh, zero1=True,
        state_dir=os.environ["AUTODIST_MPMD_STATE"])

    # This DP rank's slice of every microbatch: disjoint halves, so the
    # DP-mean loss/grads equal the oracle's full-microbatch mean.
    rows = B // (M * DP)
    x_mbs = [x[j * DP * rows + rank * rows:
               j * DP * rows + (rank + 1) * rows] for j in range(M)]
    t_mbs = [tgt[j * DP * rows + rank * rows:
                 j * DP * rows + (rank + 1) * rows] for j in range(M)]

    log = f"{os.environ['AUTODIST_MPMD_LOG']}.s{stage}r{rank}"
    while runner.step < STEPS:
        loss = runner.run_step(
            x_mbs if stage == 0 else None,
            t_mbs if stage == S - 1 else None)
        with open(log, "a", encoding="utf-8") as f:
            f.write(json.dumps({
                "step": runner.step - 1, "loss": float(loss),
                "attempt": int(os.environ.get("AUTODIST_ATTEMPT", "0")),
                "fingerprint": runner.fingerprint}) + "\n")
            f.flush()
    checksum = float(sum(np.abs(np.asarray(runner.params[n], np.float64))
                         .sum() for n in names))
    with open(log, "a", encoding="utf-8") as f:
        f.write(json.dumps({"done": True, "checksum": checksum,
                            "fingerprint": runner.fingerprint}) + "\n")
    jax.distributed.shutdown()


# -- parent: orchestrate + supervise ------------------------------------------

def _launch_group(stage: int, *, workdir: str, attempt: int,
                  chaos: str) -> list:
    port = _free_port()
    procs = []
    for rank in range(DP):
        env = dict(os.environ)
        env.update({
            "AUTODIST_MPMD_ROLE": "stage",
            "AUTODIST_MPMD_STAGE": str(stage),
            "AUTODIST_MPMD_DP_RANK": str(rank),
            "AUTODIST_MPMD_COORD": f"127.0.0.1:{port}",
            "AUTODIST_MPMD_DIR": os.path.join(workdir, "acts"),
            "AUTODIST_MPMD_STATE": os.path.join(workdir, "state"),
            "AUTODIST_MPMD_LOG": os.path.join(workdir, "steps"),
            "AUTODIST_MPMD_TIMEOUT_S": "300",
            "AUTODIST_ATTEMPT": str(attempt),
            "AUTODIST_CHAOS": chaos,
        })
        procs.append(subprocess.Popen(
            [sys.executable, "-u", os.path.abspath(__file__)], env=env,
            start_new_session=True))
    return procs


def _run_job(workdir: str, *, chaos: str) -> dict:
    """One full pipeline job; supervises a chaos-killed stage group."""
    os.makedirs(os.path.join(workdir, "acts"), exist_ok=True)
    os.makedirs(os.path.join(workdir, "state"), exist_ok=True)
    groups = {st: _launch_group(st, workdir=workdir, attempt=0,
                                chaos=chaos) for st in range(S)}
    restarts = 0
    deadline = time.monotonic() + 540
    while time.monotonic() < deadline:
        running = [p for ps in groups.values() for p in ps
                   if p.poll() is None]
        if not running:
            break
        for st, ps in list(groups.items()):
            if any(p.poll() == KILL_CODE for p in ps):
                # The supervisor bit: a chaos-killed worker takes its
                # WHOLE stage group down (the dead rank's gloo peers
                # cannot make progress), and the group relaunches on a
                # fresh coordinator port as attempt 1.  The other
                # stage's group keeps running — it just blocks in
                # transport recv until the restarted group catches up.
                for p in ps:
                    if p.poll() is None:
                        p.terminate()
                for p in ps:
                    try:
                        p.wait(timeout=30)
                    except subprocess.TimeoutExpired:
                        p.kill()
                restarts += 1
                groups[st] = _launch_group(st, workdir=workdir,
                                           attempt=restarts, chaos=chaos)
        time.sleep(0.25)
    codes = {f"s{st}r{i}": p.returncode
             for st, ps in groups.items() for i, p in enumerate(ps)}
    for ps in groups.values():
        for p in ps:
            if p.poll() is None:
                p.kill()
    # Stitch per-step losses from the last stage's rank-0 log (the
    # DP-mean loss is identical on every rank); a step may appear twice
    # (pre-kill + replayed) — the LAST entry is the surviving timeline.
    losses: dict = {}
    checksums = {}
    fingerprints = set()
    for st in range(S):
        for r in range(DP):
            path = os.path.join(workdir, f"steps.s{st}r{r}")
            if not os.path.exists(path):
                continue
            with open(path, encoding="utf-8") as f:
                for line in f:
                    rec = json.loads(line)
                    if rec.get("fingerprint"):
                        fingerprints.add(rec["fingerprint"])
                    if rec.get("done"):
                        checksums[f"s{st}r{r}"] = rec["checksum"]
                    elif st == S - 1:
                        losses[int(rec["step"])] = float(rec["loss"])
    return {"losses": [losses.get(k) for k in range(STEPS)],
            "checksums": checksums, "restarts": restarts,
            "exit_codes": codes,
            "fingerprints": sorted(fingerprints)}


def _oracle() -> dict:
    """Single-program one_f_one_b reference on a pipe=2 mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    jax.config.update("jax_platforms", "cpu")

    from autodist_tpu.mesh import build_mesh
    from autodist_tpu.parallel.mpmd import partition_params
    from autodist_tpu.parallel.pipeline_1f1b import one_f_one_b

    layers, x, tgt = _case()
    part, _ = partition_params(layers, S)
    sp = {"w": np.stack([np.stack([layers[j]["w"] for j in run])
                         for run in part.layers]),
          "b": np.stack([np.stack([layers[j]["b"] for j in run])
                         for run in part.layers])}

    def sfn(p, h):
        for j in range(p["w"].shape[0]):
            h = jnp.tanh(h @ p["w"][j] + p["b"][j])
        return h

    def mse(y, t):
        return jnp.mean((y - t) ** 2)

    mesh = build_mesh({"pipe": S}, devices=jax.devices()[:S])
    cur = {k: jnp.asarray(v) for k, v in sp.items()}
    losses = []
    for _ in range(STEPS):
        loss, d, _ = one_f_one_b(sfn, mse, cur, jnp.asarray(x),
                                 jnp.asarray(tgt), mesh,
                                 num_microbatches=M)
        cur = jax.tree_util.tree_map(
            lambda p, g: (p.astype(jnp.float32)
                          - LR * g.astype(jnp.float32)).astype(p.dtype),
            cur, d)
        losses.append(float(loss))
    checksum = float(sum(np.abs(np.asarray(v, np.float64)).sum()
                         for v in cur.values()))
    return {"losses": losses, "checksum": checksum}


def main() -> None:
    base = os.environ["AUTODIST_MPMD_WORKDIR"]
    chaos_spec = f"kill@step=1,proc=0,attempt=0,stage={S - 1}"
    clean = _run_job(os.path.join(base, "clean"), chaos="")
    chaos = _run_job(os.path.join(base, "chaos"), chaos=chaos_spec)
    oracle = _oracle()
    report = {"clean": clean, "chaos": chaos, "oracle": oracle}
    with open(os.environ["AUTODIST_RESULT_FILE"], "w",
              encoding="utf-8") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({"clean_losses": clean["losses"],
                      "chaos_losses": chaos["losses"],
                      "oracle_losses": oracle["losses"],
                      "restarts": chaos["restarts"]}), flush=True)


if __name__ == "__main__":
    if os.environ.get("AUTODIST_MPMD_ROLE") == "stage":
        stage_worker()
    else:
        main()
