"""MPMD pipeline runtime: per-stage programs on separate slices with
DCN activation transport (docs/pipeline.md).

Three pieces, one verified program:

* :mod:`.partition` — the stage partitioner and
  :func:`~autodist_tpu.parallel.mpmd.partition.build_pipeline_ir`, THE
  shared schedule-IR constructor (runtime, analyzer and ``--simulate``
  all call it, so static and runtime fingerprints agree by
  construction);
* :mod:`.transport` — the DCN activation/gradient plane (atomic
  digest-checked blobs with an in-memory fast path, on the PR 12 retry
  transport);
* :mod:`.runner` — the per-stage 1F1B jit loop with flight-recorder
  cursors on every transport leg and ZeRO-1 bucketed sync within the
  stage.
"""
from autodist_tpu.parallel.mpmd.partition import (
    RULE_STAGE_MISMATCH,
    PipelineProgram,
    StagePartition,
    assign_layers,
    build_pipeline_ir,
    catalog_from_layers,
    partition_catalog,
    partition_params,
    preflight_stage_resize,
    restage_params,
    stage_mismatch_reason,
    strip_stage,
)
from autodist_tpu.parallel.mpmd.transport import (
    ActivationTransport,
    TransportTimeout,
)


def __getattr__(name):
    # The runner is the only jax-importing piece; load it lazily so the
    # mesh-free consumers (--simulate sweeps, the analyzer, the
    # verifier goldens) can use the partitioner without paying — or
    # even having — a jax import.
    if name in ("StageRunner", "make_zero1_update"):
        from autodist_tpu.parallel.mpmd import runner
        return getattr(runner, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "ActivationTransport",
    "PipelineProgram",
    "RULE_STAGE_MISMATCH",
    "StagePartition",
    "StageRunner",
    "TransportTimeout",
    "assign_layers",
    "build_pipeline_ir",
    "catalog_from_layers",
    "make_zero1_update",
    "partition_catalog",
    "partition_params",
    "preflight_stage_resize",
    "restage_params",
    "stage_mismatch_reason",
    "strip_stage",
]
