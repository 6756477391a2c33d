"""Fast-recovery checkpoint tiers: host-RAM snapshots, peer mirrors,
restore routing (docs/resilience.md, docs/checkpoints.md).

At pod scale, preemptions and host failures are operating conditions,
not exceptions — yet the persistent Orbax tier alone makes every
recovery cost minutes of shared-disk I/O plus up to ``checkpoint_every``
steps of lost work.  This module adds the cheap tiers above it:

* **RAM tier** — every ``snapshot_every`` steps, each host takes a
  device→host snapshot of its process-addressable training state
  (params + optimizer state + sync state, LOGICAL layout — the same
  layout ``Saver`` persists, so the tiers interchange) into an
  in-process :class:`SnapshotRing` of the last ``keep`` snapshots,
  digest-checked with the Saver's content-digest rule.
* **Peer tier** — each snapshot is serialized and mirrored to a buddy
  host (ring mapping: host *i*'s buddy is host *i+1*) over the existing
  ``Cluster`` retry transport (``remote_copy`` — SSH flakes retry with
  the shared ``Backoff``; local addresses degrade to a file copy, which
  is also the CPU-test path).  The mirror directory should be RAM-backed
  in production (``/dev/shm/...``): the tier's entire point is that a
  *replaced* host rejoins from a survivor's memory in seconds, without
  touching persistent storage.
* **Restore routing** — :func:`route_restore` tries RAM-local →
  peer-fetch → persistent, newest usable step wins (cheaper tier on
  ties), composing with ``preflight_elastic`` when a candidate's
  recorded mesh differs from the session's.

Work-loss bound: with a RAM snapshot every K steps, any single-host
failure loses at most K steps (vs ``checkpoint_every`` × steps/epoch
for the persistent tier alone) — the ``resilience/recovery-gap``
analysis rule warns when the persistent cadence alone exceeds the
recovery-loss budget and no RAM tier is configured.

Addressability: the RAM tier snapshots what THIS process can read
(``np.asarray`` of every leaf).  Fully-replicated state (the AllReduce
path) and single-process meshes snapshot whole; a leaf that is not
process-addressable (multi-host GSPMD shards) disables the tier with
one WARN and recovery falls through to the persistent tier — the tier
is an accelerator, never a correctness dependency.  ZeRO-1's flat
optimizer shards ARE host-owned by construction, which is what makes
them the natural unit for this tier (see docs/resilience.md).
"""
from __future__ import annotations

import io
import json
import os
import re
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from autodist_tpu.utils import logging

#: snapshot file name grammar in a peer-mirror directory.
SNAP_RE = re.compile(r"^snap_step_(\d+)\.npz$")

#: route_restore tier names, cheapest first (the tie-break order).
TIER_RAM = "ram"
TIER_PEER = "peer"
TIER_PERSISTENT = "persistent"


class SnapshotError(RuntimeError):
    """A snapshot failed to capture, serialize, or verify."""


def _tree_digest(tree: Any) -> Optional[str]:
    """The Saver's content-digest rule, shared so RAM/peer snapshots and
    persistent checkpoints can never disagree about what 'intact'
    means."""
    from autodist_tpu.checkpoint.saver import _tree_digest as digest

    return digest(tree)


@dataclass
class RamSnapshot:
    """One device→host snapshot: leaves in tree-flatten order per item
    (the restore side unflattens against the session's own target
    treedefs, exactly like a target-free Orbax restore), plus the same
    provenance ``Saver.save`` records."""

    step: int
    leaves: Dict[str, List[np.ndarray]]   # item -> flat leaves
    digest: Optional[str]
    meta: Dict[str, Any] = field(default_factory=dict)
    time: float = 0.0

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for ls in self.leaves.values() for a in ls)

    def verify(self) -> bool:
        """Recompute the content digest over the held leaves — the
        in-RAM analog of ``Saver.verify(deep=True)``."""
        if self.digest is None:
            return True   # digest was skipped at capture; nothing to check
        return _tree_digest([self.leaves[k]
                             for k in sorted(self.leaves)]) == self.digest


def capture_snapshot(session, step: Optional[int] = None,
                     extra_meta: Optional[dict] = None) -> RamSnapshot:
    """Device→host snapshot of the session's LOGICAL state.

    Synchronous by design (like the Saver's snapshot half): the training
    loop immediately donates/overwrites the live buffers, so the copy
    must complete before the next step dispatches.  Raises
    :class:`SnapshotError` when any leaf is not process-addressable."""
    import jax

    step = session.step_count if step is None else int(step)
    params_item, opt_item = session.export_state()

    def to_host(tree) -> List[np.ndarray]:
        out = []
        for leaf in jax.tree_util.tree_leaves(tree):
            try:
                out.append(np.asarray(leaf))
            except Exception as e:
                raise SnapshotError(
                    f"leaf not process-addressable ({e}); the RAM tier "
                    "needs host-readable state — recovery falls through "
                    "to the persistent tier") from e
        return out

    leaves = {"params": to_host(params_item),
              "opt_state": to_host(opt_item)}
    if jax.tree_util.tree_leaves(session.sync_state):
        leaves["sync_state"] = to_host(session.sync_state)
    meta: Dict[str, Any] = {"step": step}
    try:
        meta["mesh_axes"] = {str(k): int(v)
                             for k, v in dict(session.mesh.shape).items()}
        meta["data_axis_size"] = int(getattr(session, "data_axis_size", 1))
    except Exception:   # sessions without a mesh (tests, stubs)
        pass
    fp = getattr(session, "schedule_fingerprint", None)
    if fp:
        meta["schedule_fingerprint"] = fp
    zb = tuple(getattr(session, "zero1_buckets", ()) or ())
    if zb:
        from autodist_tpu.resilience.elastic import bucket_layout
        meta["zero1_buckets"] = bucket_layout(zb)
    if extra_meta:
        meta.update(extra_meta)
    digest = _tree_digest([leaves[k] for k in sorted(leaves)])
    return RamSnapshot(step=step, leaves=leaves, digest=digest, meta=meta,
                       time=time.time())


def load_snapshot(session, snap: RamSnapshot) -> int:
    """Restore a snapshot into the session (same-mesh path): leaves are
    unflattened against the session's own restore targets, digest
    re-checked first.  Returns the restored step."""
    import jax

    if not snap.verify():
        raise SnapshotError(
            f"snapshot step {snap.step} failed its digest re-check — "
            "refusing to restore corrupted state")
    want_axes = None
    try:
        want_axes = {str(k): int(v)
                     for k, v in dict(session.mesh.shape).items()}
    except Exception:
        pass
    have_axes = snap.meta.get("mesh_axes")
    if want_axes and have_axes and want_axes != have_axes:
        raise SnapshotError(
            f"snapshot was taken on mesh {have_axes} but this session "
            f"runs {want_axes}; RAM/peer snapshots restore same-mesh "
            "only — use the persistent tier (elastic restore) across a "
            "resize")
    params_target, opt_target = session.restore_targets()

    def unflatten(target, ls: List[np.ndarray]):
        treedef = jax.tree_util.tree_structure(target)
        if treedef.num_leaves != len(ls):
            raise SnapshotError(
                f"snapshot leaf count {len(ls)} != target "
                f"{treedef.num_leaves} (program changed since capture)")
        return jax.tree_util.tree_unflatten(treedef, ls)

    params = unflatten(params_target, snap.leaves["params"])
    opt_state = unflatten(opt_target, snap.leaves["opt_state"])
    sync_state = None
    if "sync_state" in snap.leaves and \
            jax.tree_util.tree_leaves(session.sync_state):
        try:
            sync_state = unflatten(session.sync_state,
                                   snap.leaves["sync_state"])
        except SnapshotError as e:
            logging.warning(
                "snapshot sync_state does not match this session (%s); "
                "reinitializing it — resume is approximate on the "
                "compressor path", e)
    session.import_state(params, opt_state, snap.step,
                         sync_state=sync_state)
    return snap.step


# -- serialization (the peer wire format) ------------------------------------

def snapshot_to_bytes(snap: RamSnapshot) -> bytes:
    """One .npz blob: leaves under ``<item>/<index>`` keys plus a
    ``__meta__`` JSON array — self-describing, numpy-only (no pickle on
    the peer wire)."""
    arrays: Dict[str, np.ndarray] = {}
    counts = {}
    for item, ls in snap.leaves.items():
        counts[item] = len(ls)
        for i, a in enumerate(ls):
            arrays[f"{item}/{i}"] = a
    header = {"step": snap.step, "digest": snap.digest, "meta": snap.meta,
              "time": snap.time, "counts": counts}
    arrays["__meta__"] = np.frombuffer(
        json.dumps(header).encode("utf-8"), dtype=np.uint8)
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    return buf.getvalue()


def snapshot_from_bytes(data: bytes) -> RamSnapshot:
    """Inverse of :func:`snapshot_to_bytes`; raises
    :class:`SnapshotError` on a truncated/garbled blob."""
    try:
        with np.load(io.BytesIO(data), allow_pickle=False) as z:
            header = json.loads(bytes(z["__meta__"].tobytes()).decode())
            leaves = {item: [z[f"{item}/{i}"] for i in range(n)]
                      for item, n in header["counts"].items()}
    except Exception as e:
        raise SnapshotError(f"unreadable snapshot blob: {e}") from e
    return RamSnapshot(step=int(header["step"]), leaves=leaves,
                       digest=header.get("digest"),
                       meta=header.get("meta") or {},
                       time=float(header.get("time") or 0.0))


class SnapshotRing:
    """The host-local RAM tier: last ``keep`` snapshots, newest first on
    iteration.  Pure container — capture/restore live above it."""

    def __init__(self, keep: int = 2):
        if keep < 1:
            raise ValueError("keep must be >= 1")
        self._keep = keep
        self._snaps: List[RamSnapshot] = []   # ascending by step

    def add(self, snap: RamSnapshot) -> None:
        self._snaps = [s for s in self._snaps if s.step != snap.step]
        self._snaps.append(snap)
        self._snaps.sort(key=lambda s: s.step)
        del self._snaps[:-self._keep]

    def steps(self) -> List[int]:
        return [s.step for s in self._snaps]

    def get(self, step: int) -> Optional[RamSnapshot]:
        for s in self._snaps:
            if s.step == step:
                return s
        return None

    def latest(self, verify: bool = True) -> Optional[RamSnapshot]:
        """Newest snapshot that passes its digest re-check; a corrupted
        entry is dropped (with a WARN) and the next-newest is tried —
        the in-RAM analog of ``Saver.latest_step`` skipping a damaged
        step dir."""
        for s in reversed(self._snaps):
            if not verify or s.verify():
                return s
            logging.warning(
                "RAM snapshot step %d failed its digest re-check — "
                "dropping it from the ring", s.step)
            self._snaps.remove(s)
        return None

    @property
    def nbytes(self) -> int:
        return sum(s.nbytes for s in self._snaps)

    def clear(self) -> None:
        self._snaps = []

    def __len__(self) -> int:
        return len(self._snaps)


# -- peer mirroring -----------------------------------------------------------

def buddy_of(hosts: Sequence[str], host: str) -> Optional[str]:
    """Ring buddy assignment: host *i* mirrors to host *i+1 mod n* —
    every host's state survives any single-host loss, with exactly one
    extra copy per host.  None when the host is alone or unknown."""
    hosts = list(hosts)
    if host not in hosts or len(hosts) < 2:
        return None
    return hosts[(hosts.index(host) + 1) % len(hosts)]


def _safe(name: str) -> str:
    return name.replace("/", "_").replace(":", "_")


class PeerMirror:
    """Push/fetch serialized snapshots in a mirror directory.

    ``push`` writes ``<dir>/<owner>/snap_step_<N>.npz`` — through
    ``cluster.remote_copy`` (the retry transport) when a cluster and a
    remote buddy address are given, directly otherwise (the CPU-test
    and shared-tmpfs path).  ``fetch`` reads the newest usable snapshot
    for an owner from the LOCAL view of the directory: a replaced host
    fetches its predecessor's state from the survivor that mirrors it.
    """

    def __init__(self, directory: str, cluster=None,
                 buddy: Optional[str] = None, keep: int = 2):
        self._dir = directory
        self._cluster = cluster
        self._buddy = buddy
        self._keep = max(int(keep), 1)

    @property
    def directory(self) -> str:
        return self._dir

    def _owner_dir(self, owner: str) -> str:
        return os.path.join(self._dir, _safe(owner))

    def push(self, snap: RamSnapshot, owner: str) -> str:
        """Mirror one snapshot; returns the (remote) path.  Retention
        (last ``keep``) is enforced on the destination."""
        data = snapshot_to_bytes(snap)
        dest_dir = self._owner_dir(owner)
        dest = os.path.join(dest_dir, f"snap_step_{snap.step}.npz")
        if self._cluster is not None and self._buddy is not None:
            import tempfile

            with tempfile.NamedTemporaryFile(suffix=".npz",
                                             delete=False) as f:
                f.write(data)
                tmp = f.name
            try:
                self._cluster.remote_copy(tmp, dest, self._buddy)
            finally:
                os.unlink(tmp)
        else:
            os.makedirs(dest_dir, exist_ok=True)
            tmp = dest + ".tmp"
            with open(tmp, "wb") as f:
                f.write(data)
            os.replace(tmp, dest)   # atomic: fetch never sees half a blob
        self._gc(owner)
        return dest

    def _gc(self, owner: str) -> None:
        """Drop mirrored snapshots beyond the ring depth (local view;
        remote buddies GC their own local view on their next push)."""
        steps = self.steps(owner)
        for step in steps[:-self._keep]:
            try:
                os.unlink(os.path.join(self._owner_dir(owner),
                                       f"snap_step_{step}.npz"))
            except OSError:
                pass

    def steps(self, owner: str) -> List[int]:
        try:
            names = os.listdir(self._owner_dir(owner))
        except OSError:
            return []
        return sorted(int(m.group(1)) for n in names
                      if (m := SNAP_RE.match(n)))

    def owners(self) -> List[str]:
        try:
            return sorted(n for n in os.listdir(self._dir)
                          if os.path.isdir(os.path.join(self._dir, n)))
        except OSError:
            return []

    def fetch(self, owner: str, step: Optional[int] = None
              ) -> Optional[RamSnapshot]:
        """Newest (or exact-step) usable snapshot for ``owner`` from the
        local view; unreadable/corrupt blobs are skipped with a WARN."""
        steps = self.steps(_safe(owner))
        if step is not None:
            steps = [s for s in steps if s == step]
        for s in reversed(steps):
            path = os.path.join(self._owner_dir(_safe(owner)),
                                f"snap_step_{s}.npz")
            try:
                with open(path, "rb") as f:
                    snap = snapshot_from_bytes(f.read())
            except (OSError, SnapshotError) as e:
                logging.warning("peer snapshot %s unreadable (%s) — "
                                "skipping", path, e)
                continue
            if not snap.verify():
                logging.warning("peer snapshot %s failed its digest "
                                "check — skipping", path)
                continue
            return snap
        return None

    def fetch_any(self, step: Optional[int] = None
                  ) -> Optional[RamSnapshot]:
        """Newest usable snapshot across ALL owners — the SPMD case
        where every host's state is identical (replicated params) and a
        rejoining host may take anyone's mirror."""
        best = None
        for owner in self.owners():
            snap = self.fetch(owner, step=step)
            if snap is not None and (best is None or snap.step > best.step):
                best = snap
        return best

    def clear(self, owner: Optional[str] = None) -> None:
        """Delete mirrored snapshots (all owners by default) — drill
        cleanup, so a finished drill leaves nothing in the peer store."""
        import shutil

        targets = [owner] if owner else self.owners()
        for o in targets:
            shutil.rmtree(self._owner_dir(_safe(o)), ignore_errors=True)


# -- the tier manager ---------------------------------------------------------

class CheckpointTiers:
    """Orchestrates the RAM + peer tiers around one session.

    ``on_step(step)`` is the training-loop hook (one modulo check when
    idle); ``snapshot()`` forces a capture (the emergency-preemption
    path).  ``host_id`` names this host's mirror subdirectory; the
    buddy address routes pushes over the cluster transport when given.
    """

    def __init__(self, session=None, snapshot_every: int = 0,
                 keep: int = 2, peer_dir: Optional[str] = None,
                 cluster=None, buddy: Optional[str] = None,
                 host_id: Optional[str] = None):
        self._session = session
        self.snapshot_every = int(snapshot_every)
        self.ring = SnapshotRing(keep=max(int(keep), 1))
        self.mirror = (PeerMirror(peer_dir, cluster=cluster, buddy=buddy,
                                  keep=max(int(keep), 1))
                       if peer_dir else None)
        self.host_id = host_id or self._default_host_id()
        self._disabled_reason: Optional[str] = None
        self.last_snapshot_s: Optional[float] = None

    @staticmethod
    def _default_host_id() -> str:
        try:
            import jax
            return f"proc{jax.process_index()}"
        except Exception:
            return f"proc{os.environ.get('AUTODIST_PROCESS_ID', 0)}"

    @classmethod
    def from_env(cls, session=None, checkpoint_dir: Optional[str] = None,
                 cluster=None) -> Optional["CheckpointTiers"]:
        """Build from the ``AUTODIST_SNAPSHOT_*`` env knobs; None when
        the tier is not configured (``AUTODIST_SNAPSHOT_EVERY`` unset)."""
        from autodist_tpu.const import ENV

        every = ENV.AUTODIST_SNAPSHOT_EVERY.val
        if not every:
            return None
        peer_dir = ENV.AUTODIST_SNAPSHOT_DIR.val or (
            os.path.join(checkpoint_dir, "peer_tier")
            if checkpoint_dir else None)
        return cls(session, snapshot_every=every,
                   keep=ENV.AUTODIST_SNAPSHOT_KEEP.val, peer_dir=peer_dir,
                   cluster=cluster, buddy=ENV.AUTODIST_BUDDY.val or None)

    @property
    def enabled(self) -> bool:
        return self._disabled_reason is None

    def on_step(self, step: int,
                extra_meta: Optional[dict] = None) -> Optional[RamSnapshot]:
        if (not self.snapshot_every or step <= 0
                or step % self.snapshot_every
                or self._disabled_reason is not None):
            return None
        return self.snapshot(step, extra_meta=extra_meta)

    def snapshot(self, step: Optional[int] = None,
                 extra_meta: Optional[dict] = None,
                 emergency: bool = False) -> Optional[RamSnapshot]:
        """Capture + ring + mirror.  Never raises into the training
        loop: an addressability failure disables the tier with one WARN
        (persistent recovery still works); transport failures keep the
        RAM copy and warn."""
        if self._session is None:
            raise ValueError("CheckpointTiers has no bound session")
        if self._disabled_reason is not None:
            return None
        from autodist_tpu.resilience.heartbeat import heartbeat_phase
        from autodist_tpu.telemetry import emit_event

        t0 = time.perf_counter()
        try:
            with heartbeat_phase("checkpoint/snapshot"):
                snap = capture_snapshot(self._session, step=step,
                                        extra_meta=extra_meta)
        except SnapshotError as e:
            self._disabled_reason = str(e)
            logging.warning("RAM checkpoint tier disabled: %s", e)
            emit_event("checkpoint/ram_tier_disabled", reason=str(e))
            return None
        self.ring.add(snap)
        mirrored = None
        if self.mirror is not None:
            try:
                mirrored = self.mirror.push(snap, self.host_id)
            except Exception as e:   # transport trouble: RAM copy stands
                logging.warning(
                    "peer mirror push failed for step %d (%s) — the "
                    "RAM-local copy is still held", snap.step, e)
        self.last_snapshot_s = time.perf_counter() - t0
        emit_event("checkpoint/ram_snapshot", step=snap.step,
                   bytes=snap.nbytes, ring_depth=len(self.ring),
                   mirrored=bool(mirrored), emergency=emergency,
                   duration_s=round(self.last_snapshot_s, 6))
        return snap

    def cleanup(self) -> None:
        """Drop this host's RAM ring and its mirrored files — the
        end-of-drill no-litter path."""
        self.ring.clear()
        if self.mirror is not None:
            self.mirror.clear(self.host_id)


# -- restore routing ----------------------------------------------------------

def _peer_candidates(tiers: Optional[CheckpointTiers],
                     peer_dir: Optional[str],
                     host_id: Optional[str]) -> Optional[PeerMirror]:
    if tiers is not None and tiers.mirror is not None:
        return tiers.mirror
    if peer_dir:
        return PeerMirror(peer_dir)
    return None


def route_restore(session, directory: Optional[str] = None,
                  tiers: Optional[CheckpointTiers] = None,
                  peer_dir: Optional[str] = None,
                  host_id: Optional[str] = None,
                  validate_elastic: bool = True
                  ) -> Optional[Tuple[int, str, dict]]:
    """Restore the NEWEST usable state across all tiers.

    Candidates: the RAM-local ring (this process survived), the peer
    mirror directory (this host was replaced; a survivor holds its
    state), and the persistent checkpoint under ``directory``.  Newest
    step wins; on a tie the cheaper tier does.  A candidate that fails
    (digest, mesh mismatch, truncation) falls through to the next —
    recovery never gets WORSE than the persistent tier.  Same-mesh
    snapshots restore directly; a persistent restore across a mesh
    resize runs ``preflight_elastic`` first (``validate_elastic``).

    Returns ``(step, tier, meta)`` — the restored step, the tier it
    came from, and the provenance meta that rode it (``data_state`` for
    the exact mid-epoch data resume) — or None when no tier holds
    anything usable.
    """
    from autodist_tpu.checkpoint.saver import Saver
    from autodist_tpu.telemetry import emit_event

    ram = tiers.ring.latest() if tiers is not None else None
    mirror = _peer_candidates(tiers, peer_dir, host_id)
    peer = None
    if mirror is not None:
        own = host_id or (tiers.host_id if tiers is not None
                          else CheckpointTiers._default_host_id())
        # SPMD consistency rule: every process must resume the SAME
        # step, so the candidate is the newest step visible across ALL
        # owners (a host whose own mirror lags — it died mid-cadence —
        # takes a survivor's snapshot of the newer step), preferring
        # this host's own snapshot AT that step when it exists.
        best = mirror.fetch_any()
        if best is not None:
            peer = mirror.fetch(own, step=best.step) or best
    persistent_step = (Saver.latest_step(directory)
                       if directory else None)

    candidates: List[Tuple[int, str, Any]] = []
    if ram is not None:
        candidates.append((ram.step, TIER_RAM, ram))
    if peer is not None:
        candidates.append((peer.step, TIER_PEER, peer))
    if persistent_step is not None:
        candidates.append((persistent_step, TIER_PERSISTENT, None))
    # newest step first; cheaper tier breaks ties (ram < peer <
    # persistent in cost, and the list above is appended in that order,
    # so a stable sort on -step alone preserves it).
    candidates.sort(key=lambda c: -c[0])

    for step, tier, snap in candidates:
        t0 = time.perf_counter()
        meta: dict = {}
        try:
            if tier == TIER_PERSISTENT:
                path = Saver._step_dir(directory, step)
                meta = Saver.read_meta(path)
                mesh_axes = meta.get("mesh_axes")
                try:
                    want = {str(k): int(v)
                            for k, v in dict(session.mesh.shape).items()}
                except Exception:
                    want = None
                if validate_elastic and mesh_axes and want \
                        and mesh_axes != want:
                    from autodist_tpu.resilience.elastic import \
                        preflight_elastic
                    preflight_elastic(session, meta,
                                      context=f"route_restore:{path}")
                restored = Saver(session).restore(path)
            else:
                restored = load_snapshot(session, snap)
                meta = dict(snap.meta)
        except Exception as e:
            logging.warning(
                "restore routing: %s tier step %s unusable (%s) — "
                "falling through", tier, step, e)
            continue
        emit_event("checkpoint/route_restore", tier=tier, step=restored,
                   duration_s=round(time.perf_counter() - t0, 6),
                   candidates=[[c[0], c[1]] for c in candidates])
        logging.info("restore routing: resumed step %d from the %s tier",
                     restored, tier)
        return restored, tier, meta
    return None
