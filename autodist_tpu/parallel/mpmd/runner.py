"""StageRunner: one MPMD stage's jit loop under the 1F1B schedule.

Each pipeline stage is its OWN program — its own process group (its
own ``jax.distributed`` world on its own slice), its own params, its
own data-parallel gradient sync — and the ONLY cross-stage coupling is
the DCN activation plane (:mod:`.transport`).  The runner executes the
interleaved 1F1B tick loop whose transport grid
:func:`~autodist_tpu.kernel.synchronization.schedule_ir.
_emit_pipeline_legs` emitted: per tick it forwards microbatch
``t - s`` and backwards microbatch ``t - 2(S-1) + s``, so only the
schedule's steady-state bubble is exposed — never an extra
serialization the IR didn't price.

The runner executes the SAME :class:`~autodist_tpu.parallel.mpmd.
partition.PipelineProgram` instance the static side verifies and
prices: ``assert_verified`` gates construction, every transport
recv/send stamps a flight-recorder cursor with the IR leg id (so
``localize_hang`` names the wedged stage and frontier ``recv_act``
leg), and the executed ``ir.fingerprint()`` is exported for the
static-vs-runtime equality assertion.

Data parallelism within a stage composes two ways, mirroring the IR's
two lowerings: per-leaf ``pmean`` (the psum-tree legs) or bucketed
ZeRO-1 — flat-packed buckets reduce-scattered over the stage's data
axis, the 1/d owner shard SGD-updated, and all-gathered back (the
``reduce_scatter`` bucket legs; :func:`make_zero1_update` is the
jitted collective, unit-testable against its d=1 degenerate form).
"""
from __future__ import annotations

import os
import tempfile
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence

import numpy as np

from autodist_tpu.const import MESH_AXIS_DATA
from autodist_tpu.kernel.synchronization import schedule_ir as sir
from autodist_tpu.parallel.mpmd.partition import PipelineProgram
from autodist_tpu.parallel.mpmd.transport import ActivationTransport
from autodist_tpu.telemetry import flightrec
from autodist_tpu.utils import logging


def _step_ns(step: int) -> str:
    """Transport namespace for one step: buffers are reused every step,
    so the step tag keeps step k+1's sends from colliding with step k's
    unconsumed blobs (and keeps step k's blobs re-readable for the
    chaos-restart path until :meth:`StageRunner._gc` retires them)."""
    return f"s{int(step)}/"


def make_zero1_update(mesh, lr: float, num_shards: int) -> Callable:
    """The jitted ZeRO-1 bucket update: ``(grad_stack [d, P] sharded
    over data, params_flat [P] replicated) -> new params_flat``.

    reduce-scatter the summed gradient (mean over the d data shards),
    SGD-update only this rank's 1/d owner shard, all-gather the
    updated vector — the collective sequence of the IR's
    ``reduce_scatter`` bucket legs."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    d = max(int(num_shards), 1)

    def zstep(gstack, pflat):
        g = gstack[0]                               # my rank's full grad
        gsh = lax.psum_scatter(g, MESH_AXIS_DATA,
                               scatter_dimension=0, tiled=True) / d
        i = lax.axis_index(MESH_AXIS_DATA)
        shard = pflat.shape[0] // d
        psh = lax.dynamic_slice(pflat, (i * shard,), (shard,))
        nsh = (psh - lr * gsh).astype(pflat.dtype)
        return lax.all_gather(nsh, MESH_AXIS_DATA, tiled=True)

    return jax.jit(jax.shard_map(
        zstep, mesh=mesh, in_specs=(P(MESH_AXIS_DATA), P()),
        out_specs=P(), axis_names={MESH_AXIS_DATA}, check_vma=False))


class StageRunner:
    """Drive one stage's 1F1B loop over a verified pipeline program.

    Args:
      program: the :func:`~autodist_tpu.parallel.mpmd.partition.
        build_pipeline_ir` output — the runner executes ``program.ir``
        as-is and refuses an unverifiable one.
      stage: this process group's stage index.
      stage_fn: ``(params_dict, x_mb) -> y_mb`` for THIS stage's params.
      params: the stage's flat param dict (``stage<i>/l<j>/<name>``
        keys, the :func:`partition_params` layout).
      transport: the stage's :class:`ActivationTransport` (channel
        already set to this data-parallel rank).
      loss_fn: ``(y_mb, target_mb) -> scalar`` — last stage only; the
        step loss is the MEAN over microbatches (the ``one_f_one_b``
        oracle contract).
      mesh: jax mesh with a ``data`` axis when the stage group is
        data-parallel (d > 1 requires ``jax.process_count() > 1`` — one
        DP rank per process); None for d = 1.
      zero1: bucketed ZeRO-1 sync/update instead of per-leaf pmean.
      state_dir: where per-step snapshots land (enables the bit-exact
        chaos-restart path); None disables snapshotting.
      chaos: a :class:`~autodist_tpu.resilience.chaos.ChaosMonkey`
        (default: from ``AUTODIST_CHAOS``) fired at step boundaries —
        its ``stage=`` filter matches this runner via the
        ``AUTODIST_STAGE`` stamp.
    """

    def __init__(self, program: PipelineProgram, stage: int, *,
                 stage_fn: Callable, params: Mapping[str, Any],
                 transport: ActivationTransport, lr: float = 0.1,
                 loss_fn: Optional[Callable] = None, mesh: Any = None,
                 zero1: bool = False, state_dir: Optional[str] = None,
                 chaos: Any = None, step: int = 0):
        self.program = program
        self.stage = int(stage)
        self.num_stages = int(program.partition.num_stages)
        if not 0 <= self.stage < self.num_stages:
            raise ValueError(f"stage {stage} outside 0.."
                             f"{self.num_stages - 1}")
        pf = program.pipeline[0] if program.pipeline else None
        self.key = pf.key if pf else "pipe"
        self.num_microbatches = int(pf.num_microbatches if pf
                                    else program.ir.accum_steps)
        self.stage_fn = stage_fn
        self.params: Dict[str, Any] = dict(params)
        self.transport = transport
        self.lr = float(lr)
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.zero1 = bool(zero1)
        self.state_dir = state_dir
        self.step = int(step)
        self.d = int(program.ir.axes.get(MESH_AXIS_DATA, 1))
        if self.stage == self.num_stages - 1 and loss_fn is None:
            raise ValueError("last stage needs loss_fn")
        # The runtime executes EXACTLY the verified instance: gate on
        # the same verifier the analyzer runs, then export the executed
        # fingerprint for the static-vs-runtime equality assertion.
        sir.assert_verified(program.ir,
                            context=f"mpmd:{sir.stage_name(self.stage)}")
        self.fingerprint = program.ir.fingerprint()
        flightrec.set_fingerprint(self.fingerprint)
        # Stamp the stage identity: the chaos `stage=` filter, the
        # telemetry journal, and subprocesses all read this.
        os.environ["AUTODIST_STAGE"] = sir.stage_name(self.stage)
        if chaos is None:
            from autodist_tpu.resilience.chaos import ChaosMonkey

            chaos = ChaosMonkey.from_env()
        self._chaos = chaos
        self._zupdate = None
        if state_dir:
            os.makedirs(state_dir, exist_ok=True)
            self.maybe_restore()

    # -- the 1F1B tick loop ----------------------------------------------------

    def run_step(self, x_mbs: Optional[Sequence[Any]] = None,
                 tgt_mbs: Optional[Sequence[Any]] = None) -> float:
        """One training step: M microbatches through the interleaved
        1F1B schedule, gradient sync + SGD update, snapshot, chaos
        hook.  Returns the step's mean loss (0.0 off the last stage)."""
        import jax
        import jax.numpy as jnp

        s, s_n = self.stage, self.num_stages
        m_n = self.num_microbatches
        first, last = s == 0, s == s_n - 1
        if first and (x_mbs is None or len(x_mbs) != m_n):
            raise ValueError(f"stage 0 needs {m_n} input microbatches")
        if last and (tgt_mbs is None or len(tgt_mbs) != m_n):
            raise ValueError(f"last stage needs {m_n} target microbatches")
        ns = _step_ns(self.step)
        pid = f"pipe/{self.key}"
        drain = 2 * (s_n - 1)
        stash: Dict[int, Any] = {}     # mb -> (y, pullback)
        grads = None
        loss_acc = 0.0
        for t in range(sir.schedule_ticks_1f1b(s_n, m_n, 1)):
            jf = t - s
            jb = t - drain + s
            if 0 <= jf < m_n:
                if first:
                    x_in = jnp.asarray(x_mbs[jf])
                else:
                    x_in = jnp.asarray(self._recv(
                        ns, f"act:{self.key}/f{s - 1}@{jf}",
                        f"{pid}/f{s - 1}@{jf}/recv", sir.LEG_RECV_ACT, jf,
                        from_stage=sir.stage_name(s - 1)))
                y, pull = jax.vjp(
                    lambda p, xx: self.stage_fn(p, xx), self.params, x_in)
                stash[jf] = (y, pull)
                if not last:
                    self._send(ns, f"act:{self.key}/f{s}@{jf}",
                               f"{pid}/f{s}@{jf}/send", sir.LEG_SEND_ACT,
                               jf, y, to_stage=sir.stage_name(s + 1))
            if 0 <= jb < m_n:
                y, pull = stash.pop(jb)
                if last:
                    loss_j, lpull = jax.vjp(
                        lambda yy: self.loss_fn(yy, tgt_mbs[jb]), y)
                    (ct,) = lpull(jnp.ones_like(loss_j) / m_n)
                    loss_acc += float(loss_j) / m_n
                else:
                    ct = jnp.asarray(self._recv(
                        ns, f"act:{self.key}/b{s}@{jb}",
                        f"{pid}/b{s}@{jb}/recv", sir.LEG_RECV_ACT, jb,
                        from_stage=sir.stage_name(s + 1)), y.dtype)
                dp, dx = pull(ct)
                grads = dp if grads is None else jax.tree_util.tree_map(
                    lambda a, b: a + b, grads, dp)
                if not first:
                    self._send(ns, f"act:{self.key}/b{s - 1}@{jb}",
                               f"{pid}/b{s - 1}@{jb}/send",
                               sir.LEG_SEND_ACT, jb, dx,
                               to_stage=sir.stage_name(s - 1))
        loss = self._sync_and_update(grads, loss_acc)
        self.step += 1
        if self.state_dir:
            self.save_state()
        self._chaos.on_step(self.step - 1)
        self._gc()
        return loss

    def _recv(self, ns: str, buf: str, leg: str, leg_kind: str,
              slot: int, *, from_stage: str) -> np.ndarray:
        flightrec.record_cursor(leg, kind="leg", leg_kind=leg_kind,
                                slot=slot, event="enter", step=self.step)
        try:
            return self.transport.recv(ns + buf, from_stage=from_stage)
        finally:
            flightrec.record_cursor(leg, kind="leg", leg_kind=leg_kind,
                                    slot=slot, event="exit",
                                    step=self.step)

    def _send(self, ns: str, buf: str, leg: str, leg_kind: str,
              slot: int, value: Any, *, to_stage: str) -> None:
        flightrec.record_cursor(leg, kind="leg", leg_kind=leg_kind,
                                slot=slot, event="enter", step=self.step)
        self.transport.send(ns + buf, np.asarray(value), to_stage=to_stage)
        flightrec.record_cursor(leg, kind="leg", leg_kind=leg_kind,
                                slot=slot, event="exit", step=self.step)

    def _gc(self) -> None:
        """Retire the PREVIOUS step's transport blobs: the just-
        finished step's stay published so a chaos-restarted peer can
        replay it (transport.recv's non-consuming contract)."""
        if self.step >= 2:
            self.transport.gc(_step_ns(self.step - 2))

    # -- gradient sync + update ------------------------------------------------

    def _sync_and_update(self, grads, loss_local: float) -> float:
        import jax
        import jax.numpy as jnp

        names = sorted(self.params)
        if self.d <= 1:
            for n in names:
                p = np.asarray(self.params[n])
                g = np.asarray(grads[n], np.float32)
                self.params[n] = jnp.asarray(
                    (p.astype(np.float32) - self.lr * g).astype(p.dtype))
            return loss_local
        if jax.process_count() <= 1:
            raise RuntimeError(
                "StageRunner data parallelism maps one DP rank per "
                "process; build the stage group with jax.distributed "
                "(d=%d, process_count=1)" % self.d)
        from jax.sharding import NamedSharding, PartitionSpec as P

        shard = NamedSharding(self.mesh, P(MESH_AXIS_DATA))
        rep = NamedSharding(self.mesh, P())
        # step loss: mean over the stage group's DP ranks
        lstack = jax.make_array_from_process_local_data(
            shard, np.asarray([loss_local], np.float32))
        loss = float(jax.jit(lambda a: jnp.mean(a),
                             out_shardings=rep)(lstack))
        if self.zero1 and self.program.ir.buckets:
            self._zero1_update(grads)
        else:
            # per-leaf pmean — the per-variable psum-tree lowering
            mean = jax.jit(lambda a: jnp.mean(a, axis=0),
                           out_shardings=rep)
            for n in names:
                g = np.asarray(grads[n], np.float32)
                gstack = jax.make_array_from_process_local_data(
                    shard, g[None])
                gm = np.asarray(mean(gstack))
                p = np.asarray(self.params[n])
                self.params[n] = jnp.asarray(
                    (p - self.lr * gm).astype(p.dtype))
        return loss

    def _zero1_update(self, grads) -> None:
        """Bucketed ZeRO-1: pack this stage's grads/params into the
        IR's planned flat buckets, run the reduce-scatter → shard
        update → all-gather collective, unpack."""
        import jax
        import jax.numpy as jnp

        if self._zupdate is None:
            self._zupdate = make_zero1_update(self.mesh, self.lr, self.d)
        from jax.sharding import NamedSharding, PartitionSpec as P

        shard = NamedSharding(self.mesh, P(MESH_AXIS_DATA))
        mine = set(self.params)
        for node in self.program.ir.buckets:
            members = [v for v in node["vars"] if v["name"] in mine]
            if not members:
                continue   # another stage's bucket
            pt = int(node["padded_total"])
            gflat = np.zeros((pt,), np.float32)
            pflat = np.zeros((pt,), np.float32)
            off = 0
            spans = []
            for v in members:
                arr = np.asarray(grads[v["name"]], np.float32).ravel()
                par = np.asarray(self.params[v["name"]],
                                 np.float32).ravel()
                gflat[off:off + arr.size] = arr
                pflat[off:off + par.size] = par
                spans.append((v["name"], off, arr.size))
                off += arr.size
            gstack = jax.make_array_from_process_local_data(
                shard, gflat[None])
            pnew = np.asarray(self._zupdate(gstack, jnp.asarray(pflat)))
            for name, start, size in spans:
                p = np.asarray(self.params[name])
                self.params[name] = jnp.asarray(
                    pnew[start:start + size].reshape(p.shape)
                    .astype(p.dtype))

    # -- snapshots (the chaos-restart path) ------------------------------------

    def _state_path(self) -> str:
        return os.path.join(self.state_dir,
                            f"{sir.stage_name(self.stage)}"
                            f"_{self.transport.channel or 'dp0'}.npz")

    def meta(self) -> dict:
        """What :func:`~autodist_tpu.parallel.mpmd.partition.
        preflight_stage_resize` needs to validate a stage-count change
        against this run."""
        pf = self.program.pipeline[0] if self.program.pipeline else None
        return {"partition": self.program.partition.to_meta(),
                "num_microbatches": int(self.num_microbatches),
                "act_nbytes": int(pf.act_nbytes) if pf else 0,
                "act_dtype": pf.dtype if pf else "float32",
                "key": self.key, "zero1": self.zero1,
                "schedule_fingerprint": self.fingerprint}

    def save_state(self) -> str:
        path = self._state_path()
        fd, tmp = tempfile.mkstemp(dir=self.state_dir, suffix=".tmp.npz")
        os.close(fd)
        arrays = {f"param:{n}": np.asarray(v)
                  for n, v in self.params.items()}
        np.savez(tmp, step=np.int64(self.step), **arrays)
        os.replace(tmp, path)   # atomic publish, the transport idiom
        return path

    def maybe_restore(self) -> bool:
        """Load the newest snapshot if one exists (the supervisor
        restart path); bit-exact — params land with their saved bytes."""
        import jax.numpy as jnp

        path = self._state_path()
        if not os.path.exists(path):
            return False
        try:
            with np.load(path, allow_pickle=False) as z:
                step = int(z["step"])
                params = {k[len("param:"):]: np.array(z[k])
                          for k in z.files if k.startswith("param:")}
        except Exception as e:
            logging.warning("mpmd: snapshot %s unreadable (%s); "
                            "starting fresh", path, e)
            return False
        if sorted(params) != sorted(self.params):
            logging.warning("mpmd: snapshot %s param catalog mismatch; "
                            "starting fresh", path)
            return False
        self.params = {n: jnp.asarray(v) for n, v in params.items()}
        self.step = step
        logging.info("mpmd: %s restored step %d from %s",
                     sir.stage_name(self.stage), step, path)
        return True
