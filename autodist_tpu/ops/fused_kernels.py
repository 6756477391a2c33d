"""Fused Pallas TPU kernels for the sync and serving paths.

What each fuses away is arithmetic the unfused lowering does in passes
of its own (none of the four has a time on the chip yet: ROADMAP.md S4):
the numerics guard's detection is a second pass over every bucket (the
rollup psum is one small collective), quantize/dequantize work sits at
every ring-hop boundary (EQuARX, arXiv:2506.17615, fuses exactly this
into the collective), the ZeRO-1 shard update is the classic fusion
target of weight-update sharding (arXiv:2004.13336), and serving's paged
decode still gathers the whole KV window per layer per tick.  Four
kernels delete that arithmetic by fusion:

1. **Fused bucket pack + finiteness detect** (:func:`fused_pack_detect`
   / :func:`fused_detect_stats`): ONE pass over the packed bucket
   producing both guard statistics — the non-finite element count and
   the squared-norm partial — that ``numerics/guard.py`` otherwise
   computes as two separate full-vector reductions inside
   ``explicit_sync.py``.  The guard becomes a byproduct of the pack.
2. **Fused unscale/clip/update** (:func:`fused_adam_update`): the
   loss-scale unscale, the global-norm clip factor (one multiplier,
   computed from the guard psum), and the Adam moment + parameter math
   of the ZeRO-1 flat bucket-major shard in one elementwise kernel —
   one HBM read and write of (p, g, m, v) instead of the optax chain's
   per-transform passes.  Exact vs the unsharded optax chain at 1e-6
   (the PR 5 contract); requires the program's optimizer to be
   :func:`fusable_adam` so the hyperparameters are known statically.
3. **Fused quantize hop** (:func:`fused_quantize` /
   :func:`fused_hop_accumulate` / :func:`fused_dequant_add`): each
   quantized ring hop's dequantize → accumulate-f32 → requantize
   (``quant_ring.py``) as one kernel over the per-chunk scale grid —
   the f32 partial lives only in VMEM between the wire formats, and the
   scale/clip arithmetic is the SAME shared rule
   (``ops/quant_scale.py``) the unfused compressors apply, so the two
   paths agree to float round-off.
4. **Paged attention** (:func:`paged_attention`): decode attention
   reading K/V directly through the block table (scalar-prefetch index
   maps — the block that is DMA'd is the block the table names) with
   the flash-attention online-softmax structure, replacing
   ``serving/paged_kv.py``'s gather-per-layer materialization of every
   slot's whole logical window.

Selection is an explicit opt-in: ``AUTODIST_FUSED_KERNELS`` names the
kernels (``all`` or a comma list of ``guard,update,quant_hop,
paged_attention``).  A requested kernel the program cannot lower has a
shared drop reason (:func:`fused_drop_reason` — the
``bucket_drop_reason`` pattern: runtime and analysis surface the same
string): on a TPU the runtime raises with it, off-TPU it WARNs and
falls back to the unfused lowering (:func:`drop_or_raise`).  ``AUTODIST_FUSED_INTERPRET=1`` forces Pallas interpret mode
off-TPU — the test escape hatch that lets the CPU mesh execute
the exact fused step (slower than XLA; never the default).  Enabled
kernels are recorded in the schedule IR (``fused_detect`` /
``fused_update`` / ``fused_hop`` legs, ``docs/schedule-ir.md``) and
priced per kind by ``estimate_ir_cost`` through
``telemetry/calibration.py``'s fused calibration kinds.

Tiling policy (interpret auto-selection, 128-lane padding) comes from
``ops/pallas_utils.py``; layout conventions follow
``ops/flash_attention.py``.
"""
from __future__ import annotations

import functools
from typing import Callable, List, NamedTuple, Optional, Tuple

from autodist_tpu.ops import pallas_utils, quant_scale

#: kernel names — the ``AUTODIST_FUSED_KERNELS`` vocabulary.
KERNEL_GUARD = "guard"
KERNEL_UPDATE = "update"
KERNEL_QUANT_HOP = "quant_hop"
KERNEL_PAGED_ATTENTION = "paged_attention"
ALL_KERNELS = (KERNEL_GUARD, KERNEL_UPDATE, KERNEL_QUANT_HOP,
               KERNEL_PAGED_ATTENTION)

#: elementwise-kernel block: 64 sublanes x 128 lanes of f32 per program.
_BLOCK_ROWS = 64
_BLOCK_ELEMS = _BLOCK_ROWS * pallas_utils.TILE

#: rows of the per-chunk scale grid one hop-kernel program covers; 32
#: sublanes keeps the int8 wire block (32, 256) at the int8 min tile.
_QROWS = 32

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# selection knobs + the shared drop-reason rule
# ---------------------------------------------------------------------------

def requested_kernels() -> frozenset:
    """The kernels ``AUTODIST_FUSED_KERNELS`` opts into (``all`` or a
    comma list); empty when the knob is unset — fusion is never
    ambient."""
    from autodist_tpu.const import ENV

    raw = (ENV.AUTODIST_FUSED_KERNELS.val or "").strip()
    if not raw:
        return frozenset()
    if raw.lower() == "all":
        return frozenset(ALL_KERNELS)
    return frozenset(p.strip() for p in raw.split(",") if p.strip())


def interpret_forced() -> bool:
    """Is the off-TPU interpret-mode escape hatch on
    (``AUTODIST_FUSED_INTERPRET=1``)?  Tests only — interpret mode
    executes the exact kernel bodies but slower than XLA."""
    from autodist_tpu.const import ENV

    return bool(ENV.AUTODIST_FUSED_INTERPRET.val)


def fused_drop_reason(kernel: str, *, on_tpu: bool,
                      interpret_ok: bool = False,
                      optimizer_fusable: bool = True,
                      adam_state_shaped: bool = True,
                      f32_buckets: bool = True) -> Optional[str]:
    """Why a REQUESTED fused kernel cannot lower on this program, or
    None when it can.  Pure — the single rule shared by the runtime
    fallback WARN and the ``schedule/fused-fallback`` analysis WARN,
    so the lint can never drift from the lowering (the
    ``bucket_drop_reason`` pattern)."""
    if kernel not in ALL_KERNELS:
        return (f"unknown fused kernel {kernel!r}; expected one of "
                f"{ALL_KERNELS}")
    if not on_tpu and not interpret_ok:
        return ("Pallas fused kernels need a TPU backend; this process "
                "is off-TPU (set AUTODIST_FUSED_INTERPRET=1 to force "
                "interpret mode — test/bench only, slower than XLA)")
    if kernel == KERNEL_UPDATE:
        if not optimizer_fusable:
            return ("the fused unscale/clip/update kernel needs the Adam "
                    "hyperparameters statically: build the optimizer with "
                    "ops.fused_kernels.fusable_adam(...) (any other optax "
                    "chain keeps the unfused shard update)")
        if not adam_state_shaped:
            return ("optimizer state is not the optax.adam shape "
                    "(ScaleByAdamState with count/mu/nu); the fused shard "
                    "update cannot address its moments")
        if not f32_buckets:
            return ("a ZeRO-1 bucket is not float32: the fused update "
                    "kernel runs the f32 moment math only (optax keeps "
                    "low-precision moments in the bucket dtype, which "
                    "the kernel would not match bit-for-bit)")
    return None


def _platform_tpu() -> bool:
    import jax

    return jax.devices()[0].platform == "tpu"


def kernels_runnable() -> Tuple[bool, bool]:
    """(on_tpu, interpret_ok) — the platform half of the drop rule."""
    return _platform_tpu(), interpret_forced()


def resolve_fused(*, guard: bool, has_rs: bool, has_quant_ring: bool,
                  optimizer_fusable: bool = False,
                  adam_state_shaped: bool = True,
                  f32_buckets: bool = True
                  ) -> Tuple[Tuple[str, ...],
                             List[Tuple[str, str]]]:
    """Resolve the training-step fused-kernel set for one program.

    Returns ``(active, drops)``: kernels that lower fused, and
    ``(kernel, reason)`` pairs for requested kernels this program must
    drop.  A requested kernel whose hot path does not exist in the
    program at all (no guard, no ZeRO-1 buckets, no quantized-ring
    buckets) is silently inapplicable, not a drop — the WARN is
    reserved for fusion that was plausibly on the table.  Pure given
    the platform pair, which is resolved here once (the same rule
    analysis applies through :func:`fused_drop_reason`)."""
    requested = requested_kernels()
    on_tpu, interp = kernels_runnable()
    active: List[str] = []
    drops: List[Tuple[str, str]] = []
    applicable = {
        KERNEL_GUARD: guard,
        KERNEL_UPDATE: has_rs,
        KERNEL_QUANT_HOP: has_quant_ring,
    }
    for kernel in (KERNEL_GUARD, KERNEL_UPDATE, KERNEL_QUANT_HOP):
        if kernel not in requested or not applicable[kernel]:
            continue
        why = fused_drop_reason(
            kernel, on_tpu=on_tpu, interpret_ok=interp,
            optimizer_fusable=optimizer_fusable,
            adam_state_shaped=adam_state_shaped,
            f32_buckets=f32_buckets)
        if why is None:
            active.append(kernel)
        else:
            drops.append((kernel, why))
    return tuple(active), drops


def drop_or_raise(where: str, kernel: str, why: str) -> None:
    """A requested fused kernel cannot lower.  On a TPU that is an error:
    the request names the kernel the run is meant to use, and a quiet
    fallback would run (and time) the unfused path under its name.
    Off-TPU, where tests and rehearsals run, it stays the shared WARN
    and the caller falls back to the unfused lowering."""
    from autodist_tpu.utils import logging

    if _platform_tpu():
        raise RuntimeError(
            f"{where}: fused kernel {kernel!r} was requested "
            f"(AUTODIST_FUSED_KERNELS) but cannot lower: {why}")
    logging.warning("%s: fused kernel %s falls back to the unfused "
                    "lowering (%s)", where, kernel, why)


def paged_attention_status() -> Tuple[bool, Optional[str]]:
    """(active, drop_reason) for the serving paged-attention kernel —
    resolved at trace time by ``serving/paged_kv.py``.  ``(False,
    None)`` when simply not requested."""
    if KERNEL_PAGED_ATTENTION not in requested_kernels():
        return False, None
    on_tpu, interp = kernels_runnable()
    why = fused_drop_reason(KERNEL_PAGED_ATTENTION, on_tpu=on_tpu,
                            interpret_ok=interp)
    return why is None, why


def _interpret(interpret: Optional[bool]) -> bool:
    return pallas_utils.resolve_interpret(interpret)


def _scalar_out_spec():
    """Whole-array SMEM spec for a ``(1, 1)`` accumulator output: Mosaic
    stores scalars to SMEM only ("Cannot store scalars to VMEM"), and an
    unblocked SMEM output lives across the sequential grid, which is
    what the init-then-accumulate kernels need."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec(memory_space=pltpu.SMEM)


# ---------------------------------------------------------------------------
# kernel 1: fused bucket pack + finiteness/sq-norm detect
# ---------------------------------------------------------------------------

def _stats_kernel(x_ref, nf_ref, sq_ref):
    """One block's guard statistics, accumulated across the sequential
    grid: non-finite element count + squared sum.  A NaN/Inf propagates
    into ``sq`` exactly as in the unfused ``sum(v*v)`` (the finite BIT
    comes from the count, so the skip decision stays bit-identical)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    nf = jnp.sum(1.0 - jnp.isfinite(x).astype(jnp.float32))
    sq = jnp.sum(x * x)

    @pl.when(i == 0)
    def _init():
        nf_ref[0, 0] = nf
        sq_ref[0, 0] = sq

    @pl.when(i > 0)
    def _acc():
        nf_ref[0, 0] += nf
        sq_ref[0, 0] += sq


def fused_detect_stats(vec, *, interpret: Optional[bool] = None):
    """One Pallas pass over flat ``vec`` → ``(nonfinite_count,
    sq_sum)`` (both f32 scalars) — the two guard statistics
    ``numerics.guard.HealthAccumulator`` needs, produced together
    instead of as two separate full-vector reductions.  Zero-pads to a
    tileable length (pad is finite and adds 0 to the square sum)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    interpret = _interpret(interpret)
    vec = jnp.ravel(vec)
    n = vec.shape[0]
    if n == 0:
        return jnp.float32(0.0), jnp.float32(0.0)
    padded = pallas_utils.pad_to(n, _BLOCK_ELEMS)
    if padded != n:
        vec = jnp.pad(vec, (0, padded - n))
    x2 = vec.reshape(-1, pallas_utils.TILE)
    grid = x2.shape[0] // _BLOCK_ROWS
    nf, sq = pl.pallas_call(
        _stats_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((_BLOCK_ROWS, pallas_utils.TILE),
                               lambda i: (i, 0))],
        out_specs=[_scalar_out_spec(), _scalar_out_spec()],
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)],
        interpret=interpret,
    )(x2)
    return nf[0, 0], sq[0, 0]


def fused_pack_detect(bucket, leaves, *, interpret: Optional[bool] = None):
    """Pack one gradient bucket AND detect in the same call: returns
    ``(vec, nonfinite_count, sq_sum)`` where ``vec`` is the padded flat
    bucket (``bucketing.pack_bucket``) and the statistics come from the
    single fused pass over it — the guard as a byproduct of the pack."""
    from autodist_tpu.kernel.synchronization.bucketing import pack_bucket

    vec = pack_bucket(bucket, leaves)
    nf, sq = fused_detect_stats(vec, interpret=interpret)
    return vec, nf, sq


# ---------------------------------------------------------------------------
# kernel 2: fused unscale/clip/Adam shard update (ZeRO-1)
# ---------------------------------------------------------------------------

class AdamSpec(NamedTuple):
    """Statically known Adam hyperparameters — what the fused update
    kernel closes over."""

    lr: float
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8


class FusedAdam(NamedTuple):
    """An optax-compatible gradient transformation whose ``init`` /
    ``update`` ARE ``optax.adam``'s (the unfused path is literally the
    optax chain) plus the :class:`AdamSpec` the fused ZeRO-1 shard
    update needs.  Built by :func:`fusable_adam`."""

    init: Callable
    update: Callable
    fused_spec: AdamSpec


def fusable_adam(lr: float, b1: float = 0.9, b2: float = 0.999,
                 eps: float = 1e-8) -> FusedAdam:
    """``optax.adam`` with its hyperparameters attached, so the fused
    unscale/clip/update kernel can lower the ZeRO-1 shard update.  Any
    program is free to use it without the fused-kernel knob — it
    behaves exactly like ``optax.adam``."""
    import optax

    base = optax.adam(lr, b1=b1, b2=b2, eps=eps)
    return FusedAdam(init=base.init, update=base.update,
                     fused_spec=AdamSpec(lr=float(lr), b1=float(b1),
                                         b2=float(b2), eps=float(eps)))


def find_adam_state(state):
    """The ``ScaleByAdamState``-shaped component (count/mu/nu) inside
    an optax state tuple, or None — the structural probe behind the
    ``adam_state_shaped`` drop reason and the fused update's state
    addressing.  Top-level components only: ``fusable_adam``'s state is
    ``(ScaleByAdamState, ...)``; a nested chain is exactly the shape
    the kernel refuses."""
    if all(hasattr(state, a) for a in ("count", "mu", "nu")):
        return state
    if isinstance(state, (tuple, list)):
        for part in state:
            if all(hasattr(part, a) for a in ("count", "mu", "nu")):
                return part
    return None


def replace_adam_state(state, new_adam):
    """``state`` with its ScaleByAdamState component swapped for
    ``new_adam`` (see :func:`find_adam_state`)."""
    if all(hasattr(state, a) for a in ("count", "mu", "nu")):
        return new_adam
    parts = []
    replaced = False
    for part in state:
        if not replaced and all(hasattr(part, a)
                                for a in ("count", "mu", "nu")):
            parts.append(new_adam)
            replaced = True
        else:
            parts.append(part)
    if isinstance(state, list):
        return parts
    if hasattr(state, "_fields"):            # NamedTuple
        return type(state)(*parts)
    return tuple(parts)


def _adam_kernel(p_ref, g_ref, m_ref, v_ref, s_ref, po_ref, mo_ref, vo_ref,
                 *, lr: float, b1: float, b2: float, eps: float):
    """One elementwise block of the fused update.  ``s_ref`` carries
    the three traced scalars: row 0 = the unscale*clip multiplier, row
    1 / row 2 = the Adam bias corrections ``1 - b^count`` (computed
    once outside — they are scalars, not per-element work).  The moment
    expressions mirror ``optax.scale_by_adam`` exactly so the fused
    shard update matches the unsharded optax chain to float round-off
    (the PR 5 ZeRO-1 exactness contract)."""
    import jax.numpy as jnp

    g = g_ref[...].astype(jnp.float32) * s_ref[0, 0]
    m = (1.0 - b1) * g + b1 * m_ref[...]
    v = (1.0 - b2) * (g * g) + b2 * v_ref[...]
    m_hat = m / s_ref[1, 0]
    v_hat = v / s_ref[2, 0]
    po_ref[...] = p_ref[...] - lr * (m_hat / (jnp.sqrt(v_hat) + eps))
    mo_ref[...] = m
    vo_ref[...] = v


def fused_adam_update(p, g, mu, nu, count, spec: AdamSpec, *,
                      mult=None, interpret: Optional[bool] = None):
    """Fused unscale/clip/Adam update of one flat f32 shard.

    ``p``/``g``/``mu``/``nu`` are the ZeRO-1 bucket-major shard vectors
    (one per bucket); ``count`` is the optax step counter BEFORE this
    step; ``mult`` the combined loss-scale-unscale × global-norm-clip
    multiplier (None = 1.0).  Returns ``(new_p, new_mu, new_nu)`` —
    exactly ``optax.adam(spec)`` applied to ``mult * g`` (1e-6; the
    counter increments once per step OUTSIDE, it is shared by every
    bucket)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    interpret = _interpret(interpret)
    n = p.shape[0]
    count_inc = (count + 1).astype(jnp.float32)
    scalars = jnp.stack([
        jnp.float32(1.0) if mult is None else mult.astype(jnp.float32),
        1.0 - jnp.float32(spec.b1) ** count_inc,
        1.0 - jnp.float32(spec.b2) ** count_inc,
    ]).reshape(3, 1)
    padded = pallas_utils.pad_to(max(n, 1), _BLOCK_ELEMS)

    def prep(x):
        x = x.astype(jnp.float32)
        if padded != n:
            x = jnp.pad(x, (0, padded - n))
        return x.reshape(-1, pallas_utils.TILE)

    rows = padded // pallas_utils.TILE
    grid = rows // _BLOCK_ROWS
    blk = pl.BlockSpec((_BLOCK_ROWS, pallas_utils.TILE), lambda i: (i, 0))
    kernel = functools.partial(_adam_kernel, lr=spec.lr, b1=spec.b1,
                               b2=spec.b2, eps=spec.eps)
    new_p, new_m, new_v = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[blk, blk, blk, blk,
                  pl.BlockSpec((3, 1), lambda i: (0, 0))],
        out_specs=[blk, blk, blk],
        out_shape=[jax.ShapeDtypeStruct((rows, pallas_utils.TILE),
                                        jnp.float32)] * 3,
        interpret=interpret,
    )(prep(p), prep(g), prep(mu), prep(nu), scalars)
    return (new_p.reshape(-1)[:n], new_m.reshape(-1)[:n],
            new_v.reshape(-1)[:n])


# ---------------------------------------------------------------------------
# kernel 3: fused quantize / dequantize at ring-hop boundaries
# ---------------------------------------------------------------------------

def _wire_dtype(fmt):
    import jax.numpy as jnp

    return jnp.int8 if fmt.name == "int8" else jnp.float8_e4m3fn


def _grid_shapes(length: int, block: int):
    """(nb, nb_pad, grid) for a flat vector on the per-chunk grid."""
    from autodist_tpu.kernel.synchronization.quant_ring import scale_count

    nb = scale_count(length, block)
    nb_pad = pallas_utils.pad_to(max(nb, 1), _QROWS)
    return nb, nb_pad, nb_pad // _QROWS


def _pad_grid(x, length: int, nb_pad: int, block: int):
    import jax.numpy as jnp

    pad = nb_pad * block - length
    if pad:
        x = jnp.pad(x, (0, pad))
    return x.reshape(nb_pad, block)


def _quant_body(acc, qo_ref, so_ref, eo_ref, sat_ref, *, qmax, rounded,
                wire_dt):
    """Shared tail of the quantize kernels: per-chunk scale grid over
    the f32 block ``acc`` [R, B] — the SAME scale/clip rule the unfused
    ``quant_ring.quantize_blocks`` applies (``ops/quant_scale.py``)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    finite = jnp.isfinite(acc)
    amax = jnp.max(jnp.where(finite, jnp.abs(acc), 0.0), axis=1)
    scale = quant_scale.chunk_scale(amax, qmax)
    y = acc / scale[:, None]
    sat = quant_scale.saturation_count(y, finite, qmax,
                                       rounded=rounded).astype(jnp.float32)
    q = quant_scale.quantize_values(y, qmax, wire_dt, rounded=rounded)
    qo_ref[...] = q
    so_ref[...] = scale[:, None]
    eo_ref[...] = acc - q.astype(jnp.float32) * scale[:, None]

    @pl.when(i == 0)
    def _init():
        sat_ref[0, 0] = sat

    @pl.when(i > 0)
    def _acc():
        sat_ref[0, 0] += sat


def _quantize_kernel(x_ref, qo_ref, so_ref, eo_ref, sat_ref, *, qmax,
                     rounded, wire_dt):
    import jax.numpy as jnp

    _quant_body(x_ref[...].astype(jnp.float32), qo_ref, so_ref, eo_ref,
                sat_ref, qmax=qmax, rounded=rounded, wire_dt=wire_dt)


def _hop_kernel(q_ref, s_ref, c_ref, qo_ref, so_ref, eo_ref, sat_ref, *,
                qmax, rounded, wire_dt):
    """dequantize(received) + own chunk + requantize — one hop boundary,
    the f32 partial never leaving VMEM between the wire formats."""
    import jax.numpy as jnp

    acc = q_ref[...].astype(jnp.float32) * s_ref[...] \
        + c_ref[...].astype(jnp.float32)
    _quant_body(acc, qo_ref, so_ref, eo_ref, sat_ref, qmax=qmax,
                rounded=rounded, wire_dt=wire_dt)


def _deq_add_kernel(q_ref, s_ref, c_ref, o_ref):
    import jax.numpy as jnp

    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...] \
        + c_ref[...].astype(jnp.float32)


def _quant_specs(block: int, with_chunk: bool):
    from jax.experimental import pallas as pl

    vec_blk = pl.BlockSpec((_QROWS, block), lambda i: (i, 0))
    scale_blk = pl.BlockSpec((_QROWS, 1), lambda i: (i, 0))
    ins = [vec_blk, scale_blk, vec_blk] if with_chunk else [vec_blk]
    outs = [vec_blk, scale_blk, vec_blk, _scalar_out_spec()]
    return ins, outs


def _run_quant(kernel, args, length: int, nb: int, nb_pad: int, grid: int,
               block: int, fmt, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    ins, outs = _quant_specs(block, with_chunk=len(args) == 3)
    q, scales, err, sat = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=ins,
        out_specs=outs,
        out_shape=[
            jax.ShapeDtypeStruct((nb_pad, block), _wire_dtype(fmt)),
            jax.ShapeDtypeStruct((nb_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((nb_pad, block), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return (q.reshape(-1)[:length], scales.reshape(-1)[:nb],
            err.reshape(-1)[:length], sat[0, 0])


def fused_quantize(x, fmt, block: int = 256, *,
                   interpret: Optional[bool] = None):
    """Quantize flat f32 ``x`` on the per-chunk scale grid with the
    error and saturation count produced in the SAME pass: ``(q, scales,
    err, sat_count)``.  ``err = x - dequantize(q, scales)`` — the
    stage-1 error-feedback residual the unfused path derives with a
    separate dequantize."""
    interpret = _interpret(interpret)
    length = x.shape[0]
    nb, nb_pad, grid = _grid_shapes(length, block)
    kernel = functools.partial(_quantize_kernel, qmax=fmt.qmax,
                               rounded=fmt.name == "int8",
                               wire_dt=_wire_dtype(fmt))
    return _run_quant(kernel, (_pad_grid(x, length, nb_pad, block),),
                      length, nb, nb_pad, grid, block, fmt, interpret)


def fused_hop_accumulate(q_in, scales_in, chunk, fmt, block: int = 256, *,
                         interpret: Optional[bool] = None):
    """One ring-hop boundary fused: dequantize the received payload,
    add this device's f32 chunk, requantize with fresh per-chunk scales
    — ``(q_out, scales_out, err, sat_count)``.  The f32 partial exists
    only inside the kernel; HBM sees wire dtype in, wire dtype out."""
    import jax.numpy as jnp

    interpret = _interpret(interpret)
    length = chunk.shape[0]
    nb, nb_pad, grid = _grid_shapes(length, block)
    sp = jnp.zeros((nb_pad, 1), jnp.float32).at[:nb, 0].set(scales_in)
    kernel = functools.partial(_hop_kernel, qmax=fmt.qmax,
                               rounded=fmt.name == "int8",
                               wire_dt=_wire_dtype(fmt))
    return _run_quant(
        kernel,
        (_pad_grid(q_in, length, nb_pad, block), sp,
         _pad_grid(chunk, length, nb_pad, block)),
        length, nb, nb_pad, grid, block, fmt, interpret)


def fused_dequant_add(q_in, scales_in, chunk, fmt, block: int = 256, *,
                      interpret: Optional[bool] = None):
    """The final hop's receive side: dequantize + accumulate only (the
    owned shard stays f32, never requantized) — flat f32 result."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    interpret = _interpret(interpret)
    length = chunk.shape[0]
    nb, nb_pad, grid = _grid_shapes(length, block)
    sp = jnp.zeros((nb_pad, 1), jnp.float32).at[:nb, 0].set(scales_in)
    vec_blk = pl.BlockSpec((_QROWS, block), lambda i: (i, 0))
    scale_blk = pl.BlockSpec((_QROWS, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        _deq_add_kernel,
        grid=(grid,),
        in_specs=[vec_blk, scale_blk, vec_blk],
        out_specs=vec_blk,
        out_shape=jax.ShapeDtypeStruct((nb_pad, block), jnp.float32),
        interpret=interpret,
    )(_pad_grid(q_in, length, nb_pad, block), sp,
      _pad_grid(chunk, length, nb_pad, block))
    return out.reshape(-1)[:length]


# ---------------------------------------------------------------------------
# kernel 4: paged attention (decode, block tables as scalar prefetch)
# ---------------------------------------------------------------------------

def _paged_attn_kernel(bt_ref, rel_ref, q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref, *, bs: int, heads: int,
                       scale: float):
    """One (slot, logical-block) program: the named block arrives via
    the scalar-prefetch index map (no gather — the DMA reads exactly
    the physical block the table points at), and an online softmax
    accumulates across the slot's logical blocks.

    Heads stay side by side on the lane axis (``D = H * Dh``): Mosaic
    takes no ``dot_general`` whose batch dimension is not leading, and a
    ``(BS, H, Dh)`` block would need a relayout to make it so.  Per-head
    sums and their broadcast back to the head's lanes are two small
    matmuls against a 0/1 head-membership matrix instead, so every
    other op is a plain 2-D elementwise or row reduction.

    Refs: q/o [1,1,D]; k/v [1,BS,D] (the table-selected block);
    scratch m/l/acc [1,D] (f32, persistent across the sequential block
    grid; a head's m and l are repeated across its Dh lanes)."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    bi = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale             # [1, D]
    k = k_ref[0].astype(jnp.float32)                     # [BS, D]
    v = v_ref[0].astype(jnp.float32)
    d = q.shape[-1]
    dh = d // heads
    hp = pallas_utils.pad_to(heads, pallas_utils.TILE)

    def membership(shape, lane_dim):
        lane = lax.broadcasted_iota(jnp.int32, shape, lane_dim)
        head = lax.broadcasted_iota(jnp.int32, shape, 1 - lane_dim)
        return ((lane >= head * dh)
                & (lane < (head + 1) * dh)).astype(jnp.float32)

    # s[p, h] = sum_d q[h, d] * k[p, h, d], then repeated over head h's
    # lanes.  HIGHEST: one operand is exactly 0/1, the other carries
    # the f32 products.
    dot = lambda a, b: jnp.dot(                          # noqa: E731
        a, b, preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST)
    s = dot(dot(q * k, membership((d, hp), 0)),
            membership((hp, d), 1))                      # [BS, D]
    pos = j * bs + lax.broadcasted_iota(jnp.int32, (bs, d), 0)
    s = jnp.where(pos <= rel_ref[bi], s, _NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_prev * corr + p.sum(axis=0, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + (p * v).sum(axis=0, keepdims=True)

    @pl.when(j == nb - 1)
    def _write():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention(q, kc, vc, bt, rel, *,
                    interpret: Optional[bool] = None):
    """Decode attention over the paged KV pool, block tables read as
    scalar prefetch.

    ``q`` [B, H, Dh] (this tick's query per slot); ``kc``/``vc``
    [NB, BS, H, Dh] (ONE layer's pool); ``bt`` [B, MAXB] int32 block
    table; ``rel`` [B] int32 logical position (positions ``0..rel``
    attend).  Returns [B, H, Dh] in ``q``'s dtype — numerically the
    gather-per-layer reference of ``serving/paged_kv.py`` (masked
    positions get exactly-zero weight; the online softmax matches the
    dense softmax to f32 round-off, the flash-attention argument)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    interpret = _interpret(interpret)
    b, h, dh = q.shape
    nblk, bs, _, _ = kc.shape
    maxb = bt.shape[1]
    d = h * dh
    kernel = functools.partial(_paged_attn_kernel, bs=bs, heads=h,
                               scale=1.0 / (dh ** 0.5))
    row = pl.BlockSpec((1, 1, d), lambda bi, j, bt_r, rel_r: (bi, 0, 0))
    blk = pl.BlockSpec((1, bs, d),
                       lambda bi, j, bt_r, rel_r: (bt_r[bi, j], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, maxb),
        in_specs=[row, blk, blk],
        out_specs=row,
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)] * 3,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, d), q.dtype),
        interpret=interpret,
    )(bt.astype(jnp.int32), rel.astype(jnp.int32), q.reshape(b, 1, d),
      kc.reshape(nblk, bs, d), vc.reshape(nblk, bs, d))
    return out.reshape(b, h, dh)
