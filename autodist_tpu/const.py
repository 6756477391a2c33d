"""Framework-wide constants and environment configuration.

TPU-native analog of the reference's ``autodist/const.py`` (reference
``autodist/const.py:32-89``): a working directory for run artifacts, name
prefixes, and a typed registry of environment variables.  Where the reference
needed gRPC port ranges and a TF collective group leader, we need none — the
JAX/PJRT distributed runtime handles rendezvous — so those knobs are replaced
by mesh-axis names and coordinator addresses.
"""
from __future__ import annotations

import enum
import os

# Root for all run artifacts (strategies, traces, graph dumps, logs).
# Reference: DEFAULT_WORKING_DIR = /tmp/autodist (autodist/const.py:32-36).
DEFAULT_WORKING_DIR = os.environ.get("AUTODIST_TPU_WORKDIR", "/tmp/autodist_tpu")
DEFAULT_STRATEGY_DIR = os.path.join(DEFAULT_WORKING_DIR, "strategies")
DEFAULT_TRACE_DIR = os.path.join(DEFAULT_WORKING_DIR, "traces")
DEFAULT_GRAPH_DIR = os.path.join(DEFAULT_WORKING_DIR, "graphs")
DEFAULT_LOG_DIR = os.path.join(DEFAULT_WORKING_DIR, "logs")
DEFAULT_CHECKPOINT_DIR = os.path.join(DEFAULT_WORKING_DIR, "checkpoints")
DEFAULT_TELEMETRY_DIR = os.path.join(DEFAULT_WORKING_DIR, "telemetry")

# Canonical mesh-axis names.  These are the TPU-native replacement for the
# reference's device lists in Strategy.graph_config.replicas: instead of
# enumerating device strings, a strategy names which mesh axes a tensor is
# partitioned over.
MESH_AXIS_DATA = "data"      # data parallelism (batch axis)
MESH_AXIS_MODEL = "model"    # tensor/model parallelism (partitioned variables)
MESH_AXIS_SEQ = "seq"        # sequence/context parallelism (ring attention)
MESH_AXIS_PIPE = "pipe"      # pipeline parallelism (stages)
MESH_AXIS_EXPERT = "expert"  # expert parallelism (MoE)

ALL_MESH_AXES = (
    MESH_AXIS_DATA,
    MESH_AXIS_MODEL,
    MESH_AXIS_SEQ,
    MESH_AXIS_PIPE,
    MESH_AXIS_EXPERT,
)

# Name-scope prefix used when the explicit (shard_map) execution path labels
# per-variable synchronization segments; analog of AUTODIST_PREFIX name scopes
# (autodist/const.py:41-49).
AUTODIST_PREFIX = "AutoDistTPU"


def _bool(v):
    return v in ("True", "true", "1")


def _str(v):
    return v or ""


def _int0(v):
    return int(v) if v else 0


def _int1(v):
    return int(v) if v else 1


def _loglevel(v):
    return v or "INFO"


def _bool_default_true(v):
    return v not in ("False", "false", "0")


def _float0(v):
    return float(v) if v else 0.0


def _int2(v):
    return int(v) if v else 2


class ENV(enum.Enum):
    """Typed environment-variable registry.

    Mirrors the reference's ``ENV`` enum (``autodist/const.py:55-89``):
    ``ENV.X.val`` returns the parsed value of environment variable ``X`` with
    a typed default.  Each member's value is ``(name, parser)`` so the
    registry is self-contained — a member cannot exist without its parser.
    (Plain-callable values don't work: functions in an Enum body become
    methods, not members.)
    """

    # non-empty ⇒ this process is a worker; value = its address
    AUTODIST_WORKER = ("AUTODIST_WORKER", _str)
    # strategy id to load instead of building (worker path)
    AUTODIST_STRATEGY_ID = ("AUTODIST_STRATEGY_ID", _str)
    AUTODIST_MIN_LOG_LEVEL = ("AUTODIST_MIN_LOG_LEVEL", _loglevel)
    # extra assertions during tests
    AUTODIST_IS_TESTING = ("AUTODIST_IS_TESTING", _bool)
    # implicit program capture inside ad.scope() (optax/jax.grad
    # interception, autodist_tpu/patch.py); analog of the reference's
    # AUTODIST_PATCH_TF gate (autodist/const.py:78)
    AUTODIST_PATCH = ("AUTODIST_PATCH", _bool_default_true)
    # print launch commands instead of executing them
    AUTODIST_DEBUG_REMOTE = ("AUTODIST_DEBUG_REMOTE", _bool)
    # profiler-trace the first N session steps (0 = off); SURVEY §5.1 parity
    # with the reference's RunOptions.trace_level timelines (runner.py:64-75)
    AUTODIST_TRACE_STEPS = ("AUTODIST_TRACE_STEPS", _int0)
    # re-armable capture windows: comma-separated step numbers at which a
    # profiler-trace window OPENS mid-run (each window spans
    # AUTODIST_TRACE_STEPS steps, min 1); windows never overlap — an open
    # window is flushed before the next one starts (utils/tracing.py)
    AUTODIST_TRACE_AT = ("AUTODIST_TRACE_AT", _str)
    # telemetry master switch (docs/observability.md): metrics registry,
    # per-step StepRecords, and the event journal.  Disabled paths are
    # near-zero-cost no-ops; the enabled overhead is unmeasured on the
    # chip beyond the session spans (PERF.md, PR 24)
    AUTODIST_TELEMETRY = ("AUTODIST_TELEMETRY", _bool_default_true)
    # when set, StepRecord ring buffers and the event journal flush as
    # JSONL under this run directory (one writer per process;
    # chief-mergeable — `python -m autodist_tpu.telemetry <dir>`)
    AUTODIST_TELEMETRY_DIR = ("AUTODIST_TELEMETRY_DIR", _str)
    # leg-calibrated cost-model constants (docs/observability.md): path
    # to a calibration.json written by telemetry.calibration
    # .save_calibration.  When set (or when
    # AUTODIST_TELEMETRY_DIR/calibration.json exists), estimate_ir_cost
    # and AutoStrategy(search=True) load the fitted constants
    # automatically — no flags.
    AUTODIST_CALIBRATION = ("AUTODIST_CALIBRATION", _str)
    # flight recorder (docs/observability.md "Flight recorder"): "0"
    # disables cursor recording entirely; "host" stamps host-phase
    # cursors only (step/checkpoint boundaries — the default
    # granularity off-TPU); "legs" additionally stamps leg-group
    # host-callbacks inside the explicit sync path; "auto" (default,
    # empty) resolves to "legs" on TPU backends (callbacks ride async
    # dispatch) and "host" elsewhere (a CPU host-callback serializes
    # the step; its cost on the chip is unmeasured).
    AUTODIST_FLIGHTREC = ("AUTODIST_FLIGHTREC", _str)
    # fused Pallas kernel opt-in (docs/kernels.md): "all" or a comma
    # list of guard,update,quant_hop,paged_attention.  Unset = every
    # path keeps its unfused lowering; requested-but-unsupported
    # configs fall back with a shared drop-reason WARN
    # (ops.fused_kernels.fused_drop_reason).
    AUTODIST_FUSED_KERNELS = ("AUTODIST_FUSED_KERNELS", _str)
    # force Pallas interpret mode off-TPU for the fused kernels —
    # the CPU test escape hatch (slower than XLA; never default)
    AUTODIST_FUSED_INTERPRET = ("AUTODIST_FUSED_INTERPRET", _bool)
    # dump staged program snapshots (plan table, StableHLO, optimized HLO);
    # parity with the reference's per-stage graph dumps
    # (kernel/graph_transformer.py:62-90)
    AUTODIST_DUMP_GRAPHS = ("AUTODIST_DUMP_GRAPHS", _bool)
    # XLA compiler-option name for the all-reduce combiner threshold;
    # when set (and the strategy carries fusable groups), the group byte
    # size is passed through as that option's value — see
    # kernel/graph_transformer.py:_combiner_bytes
    AUTODIST_COMBINER_FLAG = ("AUTODIST_COMBINER_FLAG", _str)
    # pre-flight static strategy analysis (autodist_tpu.analysis) before
    # the session builds: ERROR diagnostics raise StrategyValidationError
    # before any tracing, WARNs log once.  Also reachable per-call via
    # create_distributed_session(validate=...) / fit(validate=...).
    AUTODIST_VALIDATE = ("AUTODIST_VALIDATE", _bool)
    # Cloud-TPU pod slice: rendezvous via TPU metadata (TPUPodCluster)
    AUTODIST_TPU_POD = ("AUTODIST_TPU_POD", _bool)
    # coordinator watcher behavior on worker death: fail_fast (default) |
    # ignore | restart | supervised (resilience.supervisor.policy_from_env)
    AUTODIST_FAILURE_POLICY = ("AUTODIST_FAILURE_POLICY", _str)
    # where a supervised job's failure markers + heartbeats live (set by
    # resilience.Supervisor for each attempt)
    AUTODIST_SUPERVISOR_DIR = ("AUTODIST_SUPERVISOR_DIR", _str)
    # deterministic fault-injection spec (resilience.chaos grammar)
    AUTODIST_CHAOS = ("AUTODIST_CHAOS", _str)
    # preemption grace window in seconds (docs/resilience.md): at a
    # preemption notice, fit compares the last measured persistent-save
    # time against this deadline and routes the emergency state to the
    # peer RAM tier when a durable save cannot finish.  0 = no deadline
    # (always attempt the persistent save — the pre-tier behavior)
    AUTODIST_PREEMPT_GRACE_S = ("AUTODIST_PREEMPT_GRACE_S", _float0)
    # RAM checkpoint tier (checkpoint/tiers.py): device→host snapshot
    # cadence in steps (0 = tier off), ring depth, and the peer-mirror
    # directory (a tmpfs path like /dev/shm/... in production; any
    # shared dir in tests).  fit() arguments override all three.
    AUTODIST_SNAPSHOT_EVERY = ("AUTODIST_SNAPSHOT_EVERY", _int0)
    AUTODIST_SNAPSHOT_KEEP = ("AUTODIST_SNAPSHOT_KEEP", _int2)
    AUTODIST_SNAPSHOT_DIR = ("AUTODIST_SNAPSHOT_DIR", _str)
    # buddy host address RAM snapshots mirror to (default: the next
    # host in the ResourceSpec ring — checkpoint.tiers.buddy_of)
    AUTODIST_BUDDY = ("AUTODIST_BUDDY", _str)
    # which supervisor attempt this process belongs to (chaos/test filters)
    AUTODIST_ATTEMPT = ("AUTODIST_ATTEMPT", _int0)
    # jax.distributed coordinator (host:port)
    AUTODIST_COORDINATOR_ADDRESS = ("AUTODIST_COORDINATOR_ADDRESS", _str)
    AUTODIST_NUM_PROCESSES = ("AUTODIST_NUM_PROCESSES", _int1)
    AUTODIST_PROCESS_ID = ("AUTODIST_PROCESS_ID", _int0)
    # MPMD pipeline runtime (parallel/mpmd, docs/pipeline.md): which
    # pipeline stage this process runs (stamped by StageRunner; the
    # chaos `stage=` filter and telemetry read it), the shared
    # activation-transport directory (a tmpfs path in production; any
    # shared dir in tests), and the transport recv deadline in seconds
    AUTODIST_STAGE = ("AUTODIST_STAGE", _str)
    AUTODIST_MPMD_DIR = ("AUTODIST_MPMD_DIR", _str)
    AUTODIST_MPMD_TIMEOUT_S = ("AUTODIST_MPMD_TIMEOUT_S", _float0)
    SYS_DATA_PATH = ("SYS_DATA_PATH", _str)
    SYS_RESOURCE_PATH = ("SYS_RESOURCE_PATH", _str)

    @property
    def val(self):
        """Parsed value of the environment variable, with the typed default."""
        return self.value[1](os.environ.get(self.name))


# Worker/chief role detection, mirroring autodist/autodist.py:40-41.
def is_worker() -> bool:
    return bool(ENV.AUTODIST_WORKER.val)


def is_chief() -> bool:
    return not is_worker()
