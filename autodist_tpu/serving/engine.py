"""Continuous-batching decode engine (slot-based, TPU-first).

The reference framework is training-only; its serving story ends at
graph export (``autodist/checkpoint/saved_model_builder.py:24-64``).
This engine is beyond-parity scope: the standard production decode
loop — a fixed pool of ``slots`` sequences decoding in lockstep, where
finished sequences are harvested and new requests admitted *without
stopping the batch* — built on the same single-definition block math as
training (``models/transformer.py``) via ``models/generate._token_step``.

TPU-first design points:

* **One compiled program, static shapes.**  The engine state is a fixed
  ``[slots, window]`` token buffer and a time-major KV cache
  ``[L, window, slots, H, Dh]``.  A chunk of ``chunk`` decode ticks is
  one jitted ``lax.scan``; admission/harvest happen between chunks on
  the host.  No recompiles at request boundaries.
* **Uniform cache write index over a RING.**  Every tick writes every
  slot's K/V at the same *ring* index ``tick % window``, so the cache
  update stays the one contiguous ``dynamic_update_slice`` that makes
  the decode tick fast (the ~10× batch-major-vs-time-major lesson
  recorded in BASELINE.md) while the engine tick itself grows without
  bound.  Per-request sequence positions are recovered by offset: a
  slot admitted at tick ``start`` (an *absolute* tick, unbounded) uses
  ``pos_embed[tick - start]`` and attends the ring positions
  ``(pos - start) % window <= tick - start``.  Because ``submit``
  bounds every request's span by ``window``, a slot's live region
  never wraps onto itself, and the attended window of an active slot
  is always positions the *current* occupant wrote — so slot reuse
  needs no cache zeroing, and a free slot can admit at ANY tick: one
  long request can never stall the pool (no drain, no window reset —
  the round-4 head-of-line blocker).
* **Token-exact.**  Greedy engine output equals ``make_generator``'s
  for each request individually: the extra masked positions contribute
  exactly-zero attention weight (``exp(min - max) == 0``), so the
  numerics are identical, not approximately so (pinned in
  ``tests/test_serving_engine.py``).
* **Parallel prefill.**  Every admission (when ``prefill=True``, the
  default) charges its prompt into the cache with ONE [P]-parallel
  causal forward (``models/generate._prefill_forward`` — MXU-shaped
  matmuls) instead of P sequential decode ticks: the prompt's K/V land
  at ring positions ``(t0-P..t0-1) % window`` — behind the admission
  tick, wrapping when ``t0 < P`` — and the slot joins the global tick
  already generating.  Prefill logits equal the tick-by-tick logits up
  to float reduction order (the documented allclose-level equivalence
  of parallel vs cached attention), so greedy parity with ``generate``
  holds on non-tied argmaxes — the deterministic case the tests pin.

Admission is FIFO at chunk boundaries and always succeeds to a free
slot (a request's whole ``prompt + max_new`` span must fit inside
``window``, which is exactly the ring-safety invariant).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from autodist_tpu.models.base import ModelSpec
from autodist_tpu.models.generate import (_prefill_forward, _token_step,
                                          _vocab_size, check_sampling_args,
                                          embed_lookup, require_lm_spec,
                                          sample_next_token,
                                          unpack_lm_params)
from autodist_tpu.models.quantize import head_logits


TEMPERATURE_FLOOR = 1e-6
"""Smallest accepted nonzero per-request temperature.  Below it the
scaled logits overflow f32 (|logit|/temp > f32 max) and the softmax
NaNs, so ``submit`` rejects the range instead of silently clamping —
``temperature=0`` is the supported way to ask for greedy."""


class AdmissionError(RuntimeError):
    """Typed backpressure: a submit was rejected because the request
    queue (or an SLO class's share of it) is full.  Carries a
    ``retry_after_s`` hint derived from the observed completion rate —
    the HTTP front surfaces this as 429 with a ``Retry-After`` header
    (``serving/server.py``), and the router treats it as
    route-elsewhere, not request-failed."""

    def __init__(self, message: str, retry_after_s: float = 1.0):
        super().__init__(message)
        self.retry_after_s = float(retry_after_s)


class DeadlineError(RuntimeError):
    """Typed deadline shed: a submit was rejected because the measured
    queue-wait/per-token percentiles say the request cannot finish
    inside its ``deadline_s``.  Distinct from :class:`AdmissionError`
    (queue full): the queue may be shallow — the request itself is
    infeasible under current service rates.  The HTTP front surfaces
    this as 503 + ``Retry-After`` with ``"shed": true``, which the
    router treats as route-elsewhere WITHOUT marking the replica down
    (shedding is a load signal, not a health signal)."""

    def __init__(self, message: str, retry_after_s: float = 1.0):
        super().__init__(message)
        self.retry_after_s = float(retry_after_s)


def check_speculative_args(gamma, temperature, *, span=None,
                           window=None) -> None:
    """Submit-time validation of speculative-decoding knobs, mirroring
    the temperature-floor rule: a knob combination that would fail (or
    silently diverge) mid-run is rejected as a typed ``ValueError`` at
    submit instead.  ``gamma`` must be >= 1; greedy acceptance is only
    target-exact at ``temperature == 0``; and the verify window needs
    ``gamma`` slack positions past ``span = prompt + max_new_tokens``
    (draft proposals may overshoot before being trimmed)."""
    if int(gamma) < 1:
        raise ValueError(f"speculative gamma must be >= 1, got {gamma}")
    if float(temperature) != 0.0:
        raise ValueError(
            f"speculative decoding is greedy-only (temperature 0): "
            f"greedy acceptance guarantees target-exact output, "
            f"sampled acceptance does not; got temperature="
            f"{temperature}")
    if span is not None and window is not None \
            and span + int(gamma) > int(window):
        raise ValueError(
            f"prompt + max_new_tokens + gamma = {span + int(gamma)} "
            f"exceeds the engine window {window}; shrink gamma, "
            f"raise window=, or split the request")


def _sample_per_slot(logits, key, temp, top_k, top_p):
    """Per-slot temperature over one logits batch [B, V]: rows with
    ``temp[b] == 0`` take the argmax, others sample from
    ``logits / temp[b]`` through the engine-wide static top-k/top-p
    filters (``sample_next_token`` at temperature 1.0 on the pre-scaled
    logits — the single definition of the filters).  ``submit`` rejects
    temperatures in (0, TEMPERATURE_FLOOR), so the floor below only
    guards the greedy rows' dummy divide, never alters a request.
    The named scope prefixes the lowered ops, so a device trace names
    sampling's share of a decode tick (as ``sync_span`` does the sync
    legs)."""
    with jax.named_scope("autodist_serve/sample"):
        greedy = jnp.argmax(logits, axis=-1)
        scaled = logits.astype(jnp.float32) \
            / jnp.maximum(temp, TEMPERATURE_FLOOR)[:, None]
        sampled = sample_next_token(scaled, key, 1.0, top_k, top_p)
        return jnp.where(temp > 0.0, sampled, greedy)


# The two compiled programs live at module scope so the jit cache is
# shared across DecodeEngine instances: a server that rebuilds its
# engine (model reload, knob change) re-traces nothing that an earlier
# instance already compiled.  All configuration enters either through
# array shapes (cache layout carries L/window/slots/heads/head_dim) or
# through the static ``knobs`` tuple (top_k, top_p, prefix_len);
# temperature and eos ride as TRACED per-slot vectors (per-request
# values, no recompiles), and dispatches that don't touch the prefix
# pass prefix_len=0 + dummy kp/vp so the plain programs' compile-cache
# key is independent of any registered prefix.

@functools.partial(jax.jit, static_argnums=(0, 1),
                   donate_argnums=(3, 4, 5))
def _chunk_program(n, knobs, params, tokens, kc, vc, start, p_end, end,
                   done, active, temp, eos, use_prefix, kp, vp,
                   tick0, key):
    """``n`` decode ticks of all slots in lockstep (see DecodeEngine).

    ``temp`` [B] f32 and ``eos`` [B] i32 are TRACED per-slot sampling
    knobs (temperature 0 = greedy; eos -1 = none): per-REQUEST values
    ride through without recompiles.  ``knobs`` = (top_k, top_p, plen)
    stay static — filter branches and the registered prefix length.

    ``kp``/``vp`` [L, Ppb, H, Dh] hold the SHARED cached prefix (one
    copy, every opted-in slot attends it — ``use_prefix`` [B]); with no
    prefix registered they are [L, 1, H, Dh] zeros, plen=0, and the
    prefix math vanishes at trace time."""
    top_k, top_p, plen = knobs
    num_layers, window = kc.shape[0], kc.shape[1]
    embed, pos_embed, layer_params, ln_final = unpack_lm_params(
        params, num_layers)
    pos_idx = jnp.arange(window)[None, :]                 # [1, W]
    if plen:
        pmask = use_prefix[:, None] \
            & (jnp.arange(kp.shape[1]) < plen)[None, :]   # [B, Ppb]
        pos_off = jnp.where(use_prefix, plen, 0)          # [B]
        prefix_kv = (kp, vp)
    else:
        pmask, pos_off, prefix_kv = None, 0, None

    def one_tick(carry, i):
        tokens, kc, vc, done, key = carry
        t = tick0 + i                                     # absolute tick
        t_ring = jnp.mod(t, window)                       # ring write pos
        tok = lax.dynamic_index_in_dim(tokens, t_ring, 1, keepdims=False)
        # sequence position: prefix length offsets opted-in slots
        rel = jnp.clip(t - start, 0, window - 1) + pos_off  # [B]
        x = embed_lookup(embed, tok, pos_embed.dtype) + pos_embed[rel]
        # Ring mask: slot b attends ring positions its CURRENT occupant
        # wrote — sequence offsets 0..t-start[b], laid out mod window.
        mask = jnp.mod(pos_idx - start[:, None], window) \
            <= (t - start)[:, None]
        logits, kc, vc = _token_step(
            layer_params, ln_final, embed, x, kc, vc, t_ring, window,
            attn_mask=mask, prefix_kv=prefix_kv, prefix_mask=pmask)
        key, sub = jax.random.split(key)
        raw = _sample_per_slot(logits, sub, temp, top_k,
                               top_p).astype(tokens.dtype)
        busy = jnp.sum((active & ~done).astype(jnp.int32))
        # Teacher-force while inside the prompt; only live slots write;
        # a finished slot's buffer is left as-is (harvest pads eos on
        # the host).
        w_ring = jnp.mod(t + 1, window)
        cur = lax.dynamic_index_in_dim(tokens, w_ring, 1, keepdims=False)
        in_gen = t + 1 >= p_end                           # [B]
        live = active & ~done
        nxt = jnp.where(in_gen & live, raw, cur)
        tokens = lax.dynamic_update_index_in_dim(tokens, nxt, w_ring, 1)
        # per-slot eos (-1 = none, never matches a generated id >= 0)
        done = done | (in_gen & live & (raw == eos))
        # The final token of slot b lands at buffer index end[b]-1,
        # written by tick end[b]-2.
        done = done | (t + 2 >= end)
        return (tokens, kc, vc, done, key), busy

    (tokens, kc, vc, done, key), busy = lax.scan(
        one_tick, (tokens, kc, vc, done, key), jnp.arange(n))
    return tokens, kc, vc, done, jnp.sum(busy)


@functools.partial(jax.jit, static_argnums=(0, 1, 2),
                   donate_argnums=(4, 5, 6))
def _prefill_program(knobs, with_prefix, contiguous, params, tokens, kc,
                     vc, prompts_kpb, slot_ids, row_map, t0, p_lens, temp,
                     kp, vp, key):
    """Parallel prefill, batched over the boundary's admissions: ONE
    [K, Pb]-parallel causal forward (MXU-shaped) charges K slots' K/V
    instead of Σ P sequential ticks or K separate dispatches, and
    samples each slot's first generated token.  Each prompt lands at
    cache positions ``t0-P..t0-1`` — *behind* the shared admission tick
    — so the slots join the global tick already in generation phase;
    the token-buffer rows get the prompts and sampled tokens in the
    same program (the buffer is device-resident).  ``temp`` [slots] is
    the traced per-SLOT temperature vector (indexed by ``slot_ids`` for
    each admitted row's first sampled token).  ``prompts_kpb``
    [K, Pb]: Pb is the rows' shared pow-2 prompt bucket and K a pow-2
    sub-batch size, both chosen by the scheduler (``_flush_prefills``)
    so the set of compiled (K, Pb) programs stays small.  Writes land
    at RING positions ``(t0-P..t0-1) % window`` (``t0`` is absolute and
    ``t0 - P`` may be negative — the mod wraps both); pad positions'
    K/V and pad token writes land at ring positions >= t0 and are
    overwritten by each tick's own write before any read sees them
    (``Pb <= window``, enforced by ``_prompt_bucket``, keeps the pad
    tail off the prompt itself).  ``p_lens`` may differ per row
    (prompts right-padded to Pb).

    ``row_map`` [S] maps each target SLOT entry to its unique prompt
    row — identical prompts admitted together (system-prompt fan-out,
    n samples per prompt) are computed ONCE and their K/V scattered to
    every slot; under temperature sampling each slot still draws its
    own independent first token from the shared logits row.

    ``with_prefix`` (static): this dispatch's rows all attend the
    shared cached prefix ``kp``/``vp`` (the scheduler groups admissions
    by prefix use) — their forward runs through ``_prefill_forward``'s
    prefix seam with positions offset by the static ``plen`` in
    ``knobs``.

    ``contiguous`` (static): this dispatch's rows' ring ranges do NOT
    wrap the window (``(t0 - p_j) % window + Pb <= window``, decided on
    the host — ``_flush_prefills`` groups admissions by wrapness), so
    each row's K/V charge is ONE ``dynamic_update_slice`` spanning all
    layers — the contiguous cache write the module docstring's
    batch-major lesson is about — instead of a per-column scatter.
    Wrapped dispatches (only possible once the ring has cycled, i.e.
    ``t0 % window < p_j``) take the mod-window scatter path."""
    top_k, top_p, plen = knobs
    num_layers, _, _, heads, head_dim = kc.shape
    embed, pos_embed, layer_params, ln_final = unpack_lm_params(
        params, num_layers)
    xs, ks, vs = _prefill_forward(
        layer_params, ln_final, embed, pos_embed, prompts_kpb, heads,
        head_dim,
        prefix_kv=(kp, vp) if with_prefix else None,
        plen=plen if with_prefix else 0)
    s_count = slot_ids.shape[0]
    pb = prompts_kpb.shape[1]
    window = kc.shape[1]
    for j in range(s_count):                  # S is static (shape)
        i = row_map[j]
        row_k = lax.dynamic_index_in_dim(ks, i, 1)   # [L, 1, Pb, H, Dh]
        row_v = lax.dynamic_index_in_dim(vs, i, 1)
        p_j = p_lens[i]
        sb = slot_ids[j]
        prow = lax.dynamic_index_in_dim(prompts_kpb, i, 0)  # [1, Pb]
        if contiguous:
            # Fast path: the whole Pb range is one contiguous window
            # segment starting at (t0 - p_j) % window.
            s0 = jnp.mod(t0 - p_j, window).astype(jnp.int32)
            blk_k = jnp.swapaxes(row_k, 1, 2)     # [L, Pb, 1, H, Dh]
            blk_v = jnp.swapaxes(row_v, 1, 2)
            kc = lax.dynamic_update_slice(
                kc, blk_k.astype(kc.dtype), (0, s0, sb, 0, 0))
            vc = lax.dynamic_update_slice(
                vc, blk_v.astype(vc.dtype), (0, s0, sb, 0, 0))
            tokens = lax.dynamic_update_slice(
                tokens, prow.astype(tokens.dtype), (sb, s0))
            continue
        # Wrapped range: per-column scatter over the mod-window indices
        # (≤ 2 segments, but their lengths are traced — the scatter is
        # the shape-stable form).
        idx = jnp.mod(t0 - p_j + jnp.arange(pb), window)  # [Pb]
        kc = kc.at[:, idx, sb].set(row_k[:, 0].astype(kc.dtype))
        vc = vc.at[:, idx, sb].set(row_v[:, 0].astype(vc.dtype))
        tokens = tokens.at[sb, idx].set(prow[0].astype(tokens.dtype))
    last = jnp.take_along_axis(
        xs, (p_lens - 1)[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]                                               # [K, D]
    logits = head_logits(embed, last)                     # [K, V]
    logits_s = jnp.take(logits, row_map, axis=0)          # [S, V]
    temp_s = jnp.take(temp, slot_ids)                     # [S]
    toks = _sample_per_slot(logits_s, key, temp_s, top_k, top_p)
    t0r = jnp.mod(t0, window)
    tokens = tokens.at[slot_ids, t0r].set(toks.astype(tokens.dtype))
    # Report the values that LANDED in the buffer, not the raw draws:
    # S is padded to a pow-2 bucket with duplicated entries, and when
    # duplicate slot indices scatter different samples the winner is
    # unspecified — reading back keeps the host's eos bookkeeping
    # consistent with what the next tick will actually consume.
    landed = tokens[slot_ids, t0r]
    return tokens, kc, vc, landed


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _prefix_kv_program(params, tokens_1p, num_layers, heads, head_dim):
    """One-time K/V computation for a registered shared prefix: one
    causal forward over the (bucketed) prefix tokens, returning
    ``(kp, vp)`` each [L, Ppb, H, Dh].  Pad positions' K/V are garbage
    but masked by the static plen everywhere they could be read."""
    embed, pos_embed, layer_params, ln_final = unpack_lm_params(
        params, num_layers)
    _, ks, vs = _prefill_forward(layer_params, ln_final, embed,
                                 pos_embed, tokens_1p, heads, head_dim)
    return ks[:, 0], vs[:, 0]


@functools.lru_cache(maxsize=None)
def _sharded_zeros(shape, dtype, sharding):
    """Cached jitted zero-init producing a buffer DIRECTLY in
    ``sharding`` (never materialized on one device); cached so engine
    rebuilds re-trace nothing, like the other module-scope programs.
    Each call of the returned program yields a fresh donatable buffer."""
    return jax.jit(lambda: jnp.zeros(shape, dtype),
                   out_shardings=sharding)


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_prompt_program(tokens, prompt_pb, slot_b, t0):
    """Sequential-admission prompt write into the device-resident token
    buffer: row ``slot_b`` RING positions ``(t0..t0+Pb-1) % window``
    (pow-2 bucket; the pad tail lands on future tick-write positions of
    the same slot and is overwritten before any read sees it)."""
    idx = jnp.mod(jnp.int32(t0) + jnp.arange(prompt_pb.shape[0]),
                  tokens.shape[1])
    return tokens.at[jnp.int32(slot_b), idx].set(
        prompt_pb.astype(tokens.dtype))


@dataclass
class Request:
    """One decode request: ``prompt`` is a 1-D int array; the engine
    appends up to ``max_new_tokens`` (fewer if ``eos_id`` fires).
    ``temperature``/``eos_id`` override the engine defaults per request
    (traced per-slot values — no recompiles).  ``prefix`` PINS the
    prefix KV generation this request was submitted under —
    ``(kp, vp, plen)`` — so ``set_prefix``/``clear_prefix`` mid-flight
    can never swap cached context out from under an admitted request
    (the pin holds the old arrays alive until the last reader
    finishes)."""
    prompt: np.ndarray
    max_new_tokens: int
    request_id: int = -1
    temperature: float = 0.0
    eos_id: int = -1
    use_prefix: bool = False
    prefix: Optional[tuple] = None    # (kp, vp, plen) pinned at submit


@dataclass
class EngineStats:
    """Aggregate engine counters (monotonic over the engine lifetime)."""
    ticks: int = 0                # engine ticks executed
    busy_slot_ticks: int = 0      # sum over ticks of unfinished slots
    generated_tokens: int = 0     # tokens actually produced (post-prompt)
    prompt_tokens: int = 0        # prompt tokens consumed (all admissions)
    prefilled_tokens: int = 0     # of those, charged by parallel prefill
    prefill_admissions: int = 0   # admissions that used parallel prefill
    prefill_dispatches: int = 0   # batched prefill programs dispatched
    prefill_dedup_hits: int = 0   # slots served by a shared prompt row
    prefix_admissions: int = 0    # requests decoding against the prefix
    completed: int = 0            # requests harvested
    chunks: int = 0               # compiled-program dispatches

    @property
    def slot_utilization(self) -> float:
        """Fraction of slot-ticks spent on an unfinished request."""
        total = self.ticks * self._slots if self._slots else 0
        return self.busy_slot_ticks / total if total else 0.0

    _slots: int = field(default=0, repr=False)


class DecodeEngine:
    """Continuous-batching decode over a ``transformer_lm`` ModelSpec.

    Usage::

        eng = DecodeEngine(spec, params, slots=8, window=512)
        rid = eng.submit(prompt_1d, max_new_tokens=64)
        results = eng.run()          # {rid: np.ndarray tokens}

    ``params`` may be full precision or a weight-only int8 tree from
    :func:`autodist_tpu.models.quantize.quantize_lm_params` (the tick
    math routes through the same Pallas int8 kernel as ``generate``).

    Sampling: ``temperature`` and ``eos_id`` here are DEFAULTS that each
    ``submit(..., temperature=, eos_id=)`` may override per request —
    they ride the compiled programs as traced per-slot vectors, so mixed
    greedy/sampled batches share one program with no recompiles.
    ``top_k``/``top_p`` stay engine-wide trace-time constants (filter
    branches).  ``temperature=0`` is greedy.

    ``mesh``/``slot_axis``: multi-chip serving — shard the slot pool
    over a mesh axis (the axis size must divide ``slots``).  Per-slot
    decode has no cross-slot math, so each device decodes its own slots
    with no collectives; composes with model-axis-sharded (TP) params.
    """

    def __init__(self, spec: ModelSpec, params, *, slots: int = 8,
                 window: int = 512, chunk: int = 16,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 0.0, eos_id: Optional[int] = None,
                 rng: Optional[jax.Array] = None, prefill: bool = True,
                 mesh=None, slot_axis: str = "data",
                 max_queue: int = 1024):
        require_lm_spec(spec, "DecodeEngine")
        cfg = spec.config
        if window > cfg["max_len"]:
            raise ValueError(
                f"window={window} exceeds the model's max_len "
                f"{cfg['max_len']} (pos_embed rows)")
        if slots < 1 or window < 2 or chunk < 1:
            raise ValueError("need slots >= 1, window >= 2, chunk >= 1")
        if mesh is not None:
            if slot_axis not in mesh.axis_names:
                raise ValueError(
                    f"slot_axis {slot_axis!r} not in mesh axes "
                    f"{mesh.axis_names}")
            n_shards = mesh.shape[slot_axis]
            if slots % n_shards:
                raise ValueError(
                    f"slots={slots} must divide over the {slot_axis!r} "
                    f"axis ({n_shards} shards)")
        vocab = _vocab_size(params)
        # Same contract as make_generator (shared validation): a silent
        # fixed key would make every engine sample the identical stream.
        check_sampling_args(vocab, temperature, top_k, top_p, eos_id, rng)

        self._spec = spec
        self._params = params
        self._cfg = cfg
        self._slots = slots
        self._window = window
        self._chunk = chunk
        self._temperature = float(temperature)
        self._top_k = int(top_k)
        self._top_p = float(top_p)
        self._eos_id = -1 if eos_id is None else int(eos_id)
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        self._vocab = vocab
        self._prefill = bool(prefill)
        if max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        self._max_queue = int(max_queue)

        # Host-side scheduler state.
        self._queue: List[Request] = []
        self._next_id = 0
        self._results: Dict[int, np.ndarray] = {}
        self._slot_req: List[Optional[Request]] = [None] * slots
        self.stats = EngineStats(_slots=slots)

        self._mesh = mesh
        self._slot_axis = slot_axis
        # Multi-PROCESS serving (slot pool sharded across machines): the
        # host scheduler runs identically in every process (same inputs,
        # same numpy bookkeeping → SPMD lockstep dispatches), but host
        # pulls of device state must go through a replicating identity
        # program — a non-addressable shard (another process's slots)
        # cannot be np.array'd directly.  Single-process engines keep the
        # direct (collective-free) pulls.
        self._replicate = None
        self._replicate2 = None
        self._pull_row = None
        if mesh is not None and jax.process_count() > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            rep = NamedSharding(mesh, P())
            self._replicate = jax.jit(
                lambda x: x, out_shardings=rep)
            # done+busy replicate in ONE program: the per-chunk hot path
            # pays one collective launch, not two.
            self._replicate2 = jax.jit(
                lambda a, b: (a, b), out_shardings=(rep, rep))
            self._pull_row = jax.jit(
                lambda t, b: lax.dynamic_index_in_dim(
                    t, b, 0, keepdims=False),
                out_shardings=rep)
        self._alloc_state()

        # The static half of the compiled programs' signature (see the
        # module-level _chunk_program/_prefill_program); temperature and
        # eos ride as traced per-slot vectors; the third knob is the
        # registered prefix length (0 = none).
        self._knobs = (self._top_k, self._top_p, 0)
        self._rng_explicit = rng is not None
        # Shared prefix cache (set_prefix): K/V held ONCE, attended by
        # opted-in slots.  The dummies keep ONE program signature when
        # no prefix is registered (plen=0 erases the math at trace time).
        heads, hd = cfg["num_heads"], cfg["head_dim"]
        pdtype = self._params["pos_embed"].dtype
        self._kp0 = jnp.zeros((cfg["num_layers"], 1, heads, hd), pdtype)
        self._kp = self._vp = self._kp0
        self._prefix_tokens: Optional[np.ndarray] = None
        # The prefix generation currently decoded by ACTIVE slots:
        # admitted prefix requests all pin ONE (kp, vp, plen) tuple at a
        # time (a request pinning a DIFFERENT generation waits in the
        # queue until the last reader of the current one finishes), so
        # the chunk program's single prefix input stays well-defined
        # while set_prefix/clear_prefix swap freely mid-flight.
        self._active_prefix: Optional[tuple] = None
        self._active_prefix_users = 0
        self._prefix_pin: Optional[tuple] = None
        # Set when a device dispatch raises mid-flight: the state
        # buffers were DONATED to the failed program and may be invalid,
        # so the engine refuses further use instead of decoding garbage.
        self._poisoned = False

    def _alloc_state(self) -> None:
        """(Re)allocate the engine state.  The token buffer and KV
        cache are DEVICE-resident: the per-chunk host traffic is only
        the [B] `done` vector down and the tiny [B] metadata vectors up
        — harvest/partial pull single finished rows.  (Pulling the
        whole [B, W] buffer every chunk measurably dominated the loop
        when ticks are cheap.)  start/p_end/end/done/active live on the
        host (admission edits them in numpy)."""
        slots, window, cfg = self._slots, self._window, self._cfg
        # Drop any previous buffers BEFORE allocating: on a healthy
        # reset() the old cache is still live, and holding both would
        # transiently double device memory — an OOM at exactly the
        # cache sizes the sharded path exists to serve.
        self._tokens = self._kc = self._vc = None
        self._start = np.zeros(slots, np.int32)
        self._p_end = np.zeros(slots, np.int32)
        self._end = np.zeros(slots, np.int32)
        self._done = np.ones(slots, bool)
        self._active = np.zeros(slots, bool)
        # per-slot sampling knobs (set at admission from the request)
        self._temp = np.full(slots, self._temperature, np.float32)
        self._eos = np.full(slots, self._eos_id, np.int32)
        self._use_prefix = np.zeros(slots, bool)
        self._tick = 0
        heads, hd = cfg["num_heads"], cfg["head_dim"]
        dtype = self._params["pos_embed"].dtype
        cache_shape = (cfg["num_layers"], window, slots, heads, hd)
        if self._mesh is None:
            # Separate buffers: kc/vc are both donated to the chunk
            # program, and donating one array through two arguments is
            # an aliasing error.
            self._tokens = jnp.zeros((slots, window), jnp.int32)
            self._kc = jnp.zeros(cache_shape, dtype)
            self._vc = jnp.zeros(cache_shape, dtype)
        else:
            # Multi-chip serving: shard the SLOT pool over a mesh axis.
            # Per-slot decode has no cross-slot math, so GSPMD runs each
            # shard's slots on its own devices with no collectives in
            # the chunk program; donation keeps the shardings chunk to
            # chunk.  (With model-axis-sharded params, TP composes: the
            # per-tick einsums shard exactly as in training.)  Buffers
            # are created DIRECTLY sharded — materializing the full
            # cache on one device first would OOM exactly the multi-chip
            # cache sizes this mode exists for.
            from jax.sharding import NamedSharding, PartitionSpec as P

            row = NamedSharding(self._mesh, P(self._slot_axis))
            cache = NamedSharding(self._mesh,
                                  P(None, None, self._slot_axis))
            self._tokens = _sharded_zeros(
                (slots, window), jnp.int32, row)()
            # two separate calls -> two distinct donatable buffers
            self._kc = _sharded_zeros(cache_shape, dtype, cache)()
            self._vc = _sharded_zeros(cache_shape, dtype, cache)()

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def reset(self) -> None:
        """Drop ALL engine state — queued requests, in-flight slots,
        unfetched results — and reallocate the device buffers.  This
        also revives a poisoned engine (the compiled programs live in
        the module-scope jit cache, so recovery from a failed dispatch
        costs an allocation, not a recompile).  Call ``results()``
        first if completed-but-unfetched outputs matter."""
        self._queue.clear()
        self._results.clear()
        self._slot_req = [None] * self._slots
        self._active_prefix = None
        self._active_prefix_users = 0
        self._alloc_state()
        self._poisoned = False

    def _check_usable(self) -> None:
        if self._poisoned:
            raise RuntimeError(
                "DecodeEngine is poisoned: a device dispatch failed "
                "after its state buffers were donated (e.g. a dropped "
                "TPU connection mid-chunk); in-flight requests are "
                "lost — rebuild the engine and resubmit")

    def set_prefix(self, tokens) -> int:
        """Register a SHARED cached prefix (system prompt): its K/V are
        computed once and held as one ``[L, Pp, H, Dh]`` copy that every
        ``submit(..., use_prefix=True)`` request attends in addition to
        its own ring window — no per-slot storage, no per-admission
        recompute.  Returns the prefix length.  Replaces any previous
        prefix for FUTURE submits; requests already submitted keep the
        generation they pinned (``Request.prefix``), so a mid-flight
        swap can never change the context an admitted request decodes
        against — new-generation requests simply wait in the queue
        until the last reader of the old one finishes."""
        self._check_usable()
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if tokens.size < 1:
            raise ValueError("prefix must have at least one token")
        if not np.all((tokens >= 0) & (tokens < self._vocab)):
            raise ValueError("prefix tokens out of vocab range")
        if tokens.size + 2 > self._cfg["max_len"]:
            raise ValueError(
                f"prefix length {tokens.size} leaves no room under the "
                f"model's max_len {self._cfg['max_len']}")
        plen = int(tokens.size)
        ppb = 1 << (plen - 1).bit_length()      # pow-2 compile bucket
        if ppb > self._cfg["max_len"]:
            ppb = plen     # exact-size fallback (same rule as
            #                _prompt_bucket): the bucket's pos_embed
            #                rows must exist
        padded = np.zeros(ppb, np.int32)
        padded[:plen] = tokens
        cfg = self._cfg
        kp, vp = _prefix_kv_program(
            self._params, jnp.asarray(padded)[None],
            cfg["num_layers"], cfg["num_heads"], cfg["head_dim"])
        self._kp, self._vp = kp, vp
        self._prefix_tokens = tokens
        self._knobs = (self._top_k, self._top_p, plen)
        self._prefix_pin = (kp, vp, plen)
        return plen

    def clear_prefix(self) -> None:
        """Drop the registered prefix for FUTURE submits.  In-flight
        and queued requests keep their pinned generation — its K/V stay
        referenced through the pins and are freed (ordinary array
        refcounting) when the last reader finishes."""
        self._check_usable()
        self._kp = self._vp = self._kp0
        self._prefix_tokens = None
        self._knobs = (self._top_k, self._top_p, 0)
        self._prefix_pin = None

    @property
    def prefix_len(self) -> int:
        return 0 if self._prefix_tokens is None \
            else int(self._prefix_tokens.size)

    def submit(self, prompt, max_new_tokens: int, *,
               temperature: Optional[float] = None,
               eos_id: Optional[int] = None,
               use_prefix: bool = False) -> int:
        """Queue a request; returns its id.  ``prompt`` is 1-D ints.
        ``temperature``/``eos_id`` override the engine defaults for THIS
        request only (per-slot traced values — no recompiles); the
        top-k/top-p filters stay engine-wide.  ``use_prefix=True``
        prepends the engine's registered shared prefix (:meth:`set_prefix`)
        as cached context — the result contains only prompt+generated.

        Raises :class:`AdmissionError` (typed backpressure, carrying a
        ``retry_after_s`` hint) when the request queue is at
        ``max_queue`` — the queue is bounded so a traffic spike shows
        up as explicit rejects, not an unbounded host-memory balloon
        with minutes-deep latency."""
        self._check_usable()
        if len(self._queue) >= self._max_queue:
            raise AdmissionError(
                f"request queue full ({self._max_queue}); retry later",
                retry_after_s=self._retry_hint())
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must have at least one token")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        span = prompt.size + int(max_new_tokens)
        if span > self._window:
            raise ValueError(
                f"prompt + max_new_tokens = {span} exceeds the engine "
                f"window {self._window}; raise window= (model max_len "
                f"{self._cfg['max_len']}) or split the request")
        if use_prefix:
            if self._prefix_tokens is None:
                raise ValueError("use_prefix=True but no prefix is "
                                 "registered (call set_prefix first)")
            if self.prefix_len + span > self._cfg["max_len"]:
                raise ValueError(
                    f"prefix ({self.prefix_len}) + prompt + "
                    f"max_new_tokens ({span}) exceeds the model's "
                    f"max_len {self._cfg['max_len']} (pos_embed rows)")
        if not np.all((prompt >= 0) & (prompt < self._vocab)):
            raise ValueError("prompt tokens out of vocab range")
        if temperature is None:
            temperature = self._temperature
        else:
            temperature = float(temperature)
            if not np.isfinite(temperature) or temperature < 0.0:
                raise ValueError(f"temperature must be a finite number "
                                 f">= 0, got {temperature}")
            if temperature > 0.0 and float(np.float32(temperature)) == 0.0:
                # would underflow to exact 0 in the f32 per-slot vector
                # and silently decode greedy while "sampled" was asked
                raise ValueError(f"temperature {temperature} underflows "
                                 f"float32; use 0 for greedy or >= 1e-6")
            if 0.0 < temperature < TEMPERATURE_FLOOR:
                # below the floor the scaled logits overflow f32 and the
                # softmax NaNs; the sampler would otherwise clamp to the
                # floor, silently diverging from the requested value
                raise ValueError(
                    f"temperature {temperature} is below the sampling "
                    f"floor {TEMPERATURE_FLOOR}; use 0 for greedy or >= "
                    f"{TEMPERATURE_FLOOR}")
            if (temperature > 0.0 and self._temperature <= 0.0
                    and not self._rng_explicit):
                raise ValueError(
                    "per-request temperature sampling on a greedy-built "
                    "engine needs an explicit rng= at engine "
                    "construction (a silent fixed key would sample the "
                    "identical stream every run)")
        if eos_id is None:
            eos_id = self._eos_id
        else:
            eos_id = int(eos_id)
            # -1 explicitly DISABLES eos for this request (the program's
            # own 'none' sentinel) — the way to ask for an unterminated
            # fixed-length generation on an eos-defaulted engine.
            if eos_id != -1 and not 0 <= eos_id < self._vocab:
                raise ValueError(f"eos_id must be -1 (none) or in [0, "
                                 f"vocab_size={self._vocab}), got {eos_id}")
        req = Request(prompt, int(max_new_tokens), self._next_id,
                      temperature=temperature, eos_id=eos_id,
                      use_prefix=bool(use_prefix),
                      prefix=(self._prefix_pin if use_prefix else None))
        self._next_id += 1
        self._queue.append(req)
        return req.request_id

    def _retry_hint(self) -> float:
        """Retry-After estimate for a rejected submit: roughly how long
        until the queue has drained one request (queue depth x the
        recent per-request wall time over the slot count), clamped to
        something a client can act on."""
        per_req = self._avg_request_s or 1.0
        est = (len(self._queue) + 1) * per_req / max(self._slots, 1)
        return float(min(60.0, max(0.1, est)))

    _avg_request_s: float = 0.0

    def run(self) -> Dict[int, np.ndarray]:
        """Decode until the queue and all slots drain; returns and
        clears ``{request_id: tokens}`` (prompt included, truncated
        after a generated ``eos_id``)."""
        self._check_usable()
        while self._schedule():
            self._run_chunk()
        self._harvest()
        out, self._results = self._results, {}
        return out

    def step(self) -> bool:
        """One schedule+chunk iteration; False when fully drained.
        (``run`` is the batch wrapper; ``step`` lets a caller interleave
        submits with decoding — the continuous-batching loop proper.)"""
        self._check_usable()
        if not self._schedule():
            self._harvest()
            return False
        self._run_chunk()
        return True

    def results(self) -> Dict[int, np.ndarray]:
        """Completed results so far (and clears them).  Usable on a
        poisoned engine: already-harvested results live on the host and
        survive a failed dispatch (only in-flight work is lost)."""
        if not self._poisoned:
            self._harvest()
        out, self._results = self._results, {}
        return out

    def cancel(self, request_id: int) -> bool:
        """Cancel a queued or in-flight request.  Returns True if it was
        found and cancelled (its slot frees at the next boundary; any
        tokens already generated are discarded), False if unknown or
        already completed."""
        for qi, req in enumerate(self._queue):
            if req.request_id == request_id:
                self._queue.pop(qi)
                return True
        for b in range(self._slots):
            req = self._slot_req[b]
            if req is not None and req.request_id == request_id:
                # Freeing is host-side bookkeeping only: a freed slot
                # stops writing (done), and its cache/buffer regions are
                # overwritten by the next occupant per the module
                # invariants.
                self._active[b] = False
                self._done[b] = True
                self._unpin_slot_prefix(b)
                self._slot_req[b] = None
                return True
        return False

    def partial(self, request_id: int) -> Optional[np.ndarray]:
        """Streaming read: the tokens of an IN-FLIGHT request written so
        far (prompt included, truncated after a generated eos), as of
        the last chunk boundary.  None if the request is still queued or
        already completed (use :meth:`results` for completed ones).
        Finished slots are harvested first so a request never shows up
        both here and in ``results``."""
        self._check_usable()   # a streaming read touches device buffers
        self._harvest()
        for b in range(self._slots):
            req = self._slot_req[b]
            if req is not None and req.request_id == request_id:
                return self._slot_tokens(b)
        return None

    def _slot_tokens(self, b: int) -> np.ndarray:
        """Tokens written so far for slot ``b`` (shared by partial reads
        and harvest): absolute positions ``start..min(end, tick+1)``
        gathered from their ring images, truncated after the first eos
        GENERATED (not prompt-resident).  Pulls ONE fixed-shape row of
        the device-resident buffer (one compiled slice per slot index;
        variable bounds are applied in numpy so streaming polls don't
        accrete jit-cache entries)."""
        s, pe, e = int(self._start[b]), int(self._p_end[b]), \
            int(self._end[b])
        written = min(e, self._tick + 1)
        if self._pull_row is not None:   # cross-process slot row
            row = np.array(self._pull_row(self._tokens, jnp.int32(b)))
        else:
            row = np.array(self._tokens[b])
        seq = row[(s + np.arange(written - s)) % self._window]
        eos = int(self._eos[b])        # the slot's own (per-request) eos
        if eos >= 0:
            gen = seq[pe - s:]
            hits = np.nonzero(gen == eos)[0]
            if hits.size:
                seq = seq[:pe - s + hits[0] + 1]
        return seq

    # ------------------------------------------------------------------
    # scheduler internals
    # ------------------------------------------------------------------
    def _schedule(self) -> bool:
        """Harvest finished slots, admit queued requests (FIFO — in ring
        mode a free slot admits at ANY tick, so no fit check and no
        window reset exist).  True if a chunk should run.  Loops
        internally because a prefill admission can finish a request
        outright (max_new_tokens=1, or eos as the first token): such
        slots are harvested and refilled without running a chunk."""
        self._rebase_tick()
        while True:
            self._harvest()
            self._admit()
            # Finished-at-admission slots FIRST: free + refill them now,
            # before ticking, even while other slots are live — otherwise
            # a done slot would sit occupied through a whole chunk.
            if np.any(self._active & self._done):
                continue
            if np.any(self._active & ~self._done):
                return True
            # Pool fully idle (a free slot always admits, so an empty
            # pool means an empty queue): rewind to 0 — free (no state
            # moves; ring contents are occupant-masked).
            self._tick = 0
            return False

    _REBASE_AT = 1 << 24   # well under int32, amortized to ~never

    def _rebase_tick(self) -> None:
        """Bound absolute-tick growth under SUSTAINED load (the idle
        rewind never fires then): subtract a multiple of ``window`` from
        the tick and every slot's start/p_end/end.  Ring positions are
        ``x % window`` and masks/offsets are differences, so a shift
        that is ≡ 0 (mod window) is invisible to the device programs —
        pure host bookkeeping, O(slots), amortized to ~one shift per
        16M ticks."""
        if self._tick < self._REBASE_AT:
            return
        shift = (self._tick // self._window) * self._window
        self._tick -= shift
        self._start -= shift
        self._p_end -= shift
        self._end -= shift
        # Inactive slots' bounds are dead state (never consumed until the
        # next admission overwrites them) but would otherwise accumulate
        # -shift per rebase — a silent int32 wrap after ~2^31 total ticks
        # on a slot that never re-admits.  Zero them instead.
        inactive = ~self._active
        self._start[inactive] = 0
        self._p_end[inactive] = 0
        self._end[inactive] = 0

    def _prefix_compatible(self, req: Request) -> bool:
        """True when admitting ``req`` now keeps the one-live-prefix
        invariant: either no prefix generation is active, or ``req``
        pinned exactly that generation."""
        return (not req.use_prefix
                or self._active_prefix is None
                or req.prefix is self._active_prefix)

    def _pin_active_prefix(self, req: Request) -> None:
        if req.use_prefix:
            self._active_prefix = req.prefix
            self._active_prefix_users += 1

    def _unpin_slot_prefix(self, b: int) -> None:
        if self._use_prefix[b]:
            self._use_prefix[b] = False
            self._active_prefix_users -= 1
            if self._active_prefix_users <= 0:
                self._active_prefix_users = 0
                self._active_prefix = None   # last reader: KV now free

    def _admit(self) -> None:
        prefills: List[tuple] = []        # deferred (slot, req) pairs
        for b in range(self._slots):
            if self._active[b] or not self._queue:
                continue
            if not self._prefix_compatible(self._queue[0]):
                # Strict FIFO: the head pinned a different prefix
                # generation than the active readers'; it (and everyone
                # behind it) waits until the last old-generation reader
                # finishes.
                break
            req = self._queue.pop(0)      # FIFO: head always fits
            self._pin_active_prefix(req)
            req.t_admit = time.monotonic()
            p = req.prompt.size
            t0 = self._tick
            if self._prefill:
                # Deferred: this boundary's prefill admissions run as
                # ONE batched program (MXU-batched, one dispatch).  The
                # prompt lands BEHIND the tick at ring positions
                # (t0-P..t0-1) % window — valid even at t0 < P (the
                # slot's start tick goes negative; all position
                # arithmetic is by offset).
                prefills.append((b, req))
                continue
            # Sequential (teacher-forced) admission — the prefill=False
            # mode only (ring admission prefills unconditionally): the
            # prompt lands AHEAD of the tick and is consumed tick by
            # tick.
            try:
                self._tokens = _write_prompt_program(
                    self._tokens, self._pad_bucket(req.prompt),
                    np.int32(b), np.int32(t0))
            except Exception:
                self._poisoned = True   # tokens buffer was donated
                raise
            self._start[b] = t0
            self._p_end[b] = t0 + p
            self._end[b] = t0 + p + req.max_new_tokens
            self._done[b] = False
            self._active[b] = True
            self._temp[b] = req.temperature
            self._eos[b] = req.eos_id
            self._use_prefix[b] = req.use_prefix
            self._slot_req[b] = req
            self.stats.prompt_tokens += p
            self.stats.prefix_admissions += int(req.use_prefix)
        if prefills:
            self._flush_prefills(prefills)

    def _flush_prefills(self, group) -> None:
        """Run the boundary's prefill admissions in few, compile-bounded
        dispatches.  Rows group by their OWN pow-2 prompt bucket (a
        short prompt never pays a long prompt's padded O(Pb²) attention)
        and each bucket dispatches in pow-2-sized sub-batches; the slot
        fan-out S is pow-2 padded inside _run_prefill — so all three
        compile dimensions (Pb, K, S) are bucketed and the compiled
        program set stays logarithmic in window and slots."""
        buckets: Dict[tuple, Dict[bytes, list]] = {}
        for b, req in group:
            pb = self._prompt_bucket(req.prompt.size)
            # dedup identical prompts within a bucket: computed once,
            # K/V scattered to every requesting slot.  Prefix users
            # dispatch separately (their forward attends the shared
            # prefix and their positions are offset — a static program
            # difference).  Wrapness is likewise static (it selects the
            # contiguous-DUS vs mod-window-scatter cache write), decided
            # here with the same arithmetic the program uses; identical
            # prompts share a length, so dedup is unaffected.
            s0 = (self._tick - req.prompt.size) % self._window
            wrapped = s0 + pb > self._window
            buckets.setdefault((pb, req.use_prefix, wrapped), {}).setdefault(
                req.prompt.tobytes(), []).append((b, req))
        for (pb, with_prefix, wrapped), uniq in sorted(buckets.items()):
            entries = list(uniq.values())     # [[(b, req), ...], ...]
            while entries:
                k = 1 << (len(entries).bit_length() - 1)  # pow2 <= len
                self._run_prefill(entries[:k], pb, with_prefix, wrapped)
                entries = entries[k:]

    def _run_prefill(self, entries, pb: int, with_prefix: bool,
                     wrapped: bool = False) -> None:
        """One batched prefill dispatch over K unique prompts serving S
        slots (S >= K when prompts repeat): prompt K/V written at cache
        positions t0-P..t0-1 per slot and each first generated token
        deposited at the admission tick, so the slots start in
        generation phase.  ``with_prefix`` rows attend the shared
        cached prefix during their forward.  ``wrapped`` rows' ring
        ranges cross the window boundary and take the scatter cache
        write; all others take the contiguous fast path."""
        t0, k = self._tick, len(entries)
        prompts = np.zeros((k, pb), np.int32)
        p_lens = np.zeros(k, np.int32)
        slot_ids, row_map, flat = [], [], []
        for i, slot_reqs in enumerate(entries):
            prompt = slot_reqs[0][1].prompt
            prompts[i, :prompt.size] = prompt
            p_lens[i] = prompt.size
            for b, req in slot_reqs:
                slot_ids.append(b)
                row_map.append(i)
                flat.append((b, req))
                # per-slot knobs must land BEFORE the dispatch: the
                # program samples each slot's first token through them
                self._temp[b] = req.temperature
                self._eos[b] = req.eos_id
                self._use_prefix[b] = req.use_prefix
        slot_ids = np.asarray(slot_ids, np.int32)
        row_map = np.asarray(row_map, np.int32)
        # Pad S to its pow-2 bucket by repeating the last entry (an
        # idempotent duplicate write; the program reports landed buffer
        # values so duplicate sampling stays consistent) — S is a
        # compile dimension like K and Pb, and all three must be
        # bucketed to keep the compiled program set small.
        s_real = len(flat)
        s_pad = 1 << (s_real - 1).bit_length()
        if s_pad != s_real:
            slot_ids = np.concatenate(
                [slot_ids, np.full(s_pad - s_real, slot_ids[-1],
                                   np.int32)])
            row_map = np.concatenate(
                [row_map, np.full(s_pad - s_real, row_map[-1],
                                  np.int32)])
        self._rng, sub = jax.random.split(self._rng)
        try:
            knobs, kp, vp = self._dispatch_args(with_prefix)
            self._tokens, self._kc, self._vc, toks = _prefill_program(
                knobs, with_prefix, not wrapped, self._params,
                self._tokens, self._kc, self._vc, jnp.asarray(prompts),
                jnp.asarray(slot_ids), jnp.asarray(row_map),
                np.int32(t0), jnp.asarray(p_lens),
                jnp.asarray(self._temp), kp, vp, sub)
            if self._replicate is not None:
                toks = self._replicate(toks)
            toks = np.array(toks)
        except Exception:
            self._poisoned = True
            raise
        for j, (b, req) in enumerate(flat):
            p = req.prompt.size
            tok = int(toks[j])
            self._start[b] = t0 - p
            self._p_end[b] = t0
            self._end[b] = t0 + req.max_new_tokens
            self._done[b] = (req.max_new_tokens == 1
                             or (req.eos_id >= 0
                                 and tok == req.eos_id))
            self._active[b] = True
            self._slot_req[b] = req
            self.stats.prompt_tokens += p
            self.stats.prefilled_tokens += p
            self.stats.prefill_admissions += 1
            self.stats.prefix_admissions += int(req.use_prefix)
        self.stats.prefill_dedup_hits += len(flat) - k
        self.stats.prefill_dispatches += 1

    def _dispatch_args(self, with_prefix: bool):
        """(knobs, kp, vp) for one compiled-program dispatch — the ONE
        place encoding the compile-cache-key contract: prefix-touching
        dispatches carry the ACTIVE readers' pinned plen + K/V (which
        may be an older generation than the currently registered
        prefix — the mid-flight-swap guarantee), all others the plen=0
        knobs + dummies so their cache key is independent of any
        registered prefix."""
        if with_prefix:
            kp, vp, plen = self._active_prefix
            return (self._top_k, self._top_p, plen), kp, vp
        return (self._top_k, self._top_p, 0), self._kp0, self._kp0

    def _prompt_bucket(self, prompt_size: int) -> int:
        """Pow-2 compile bucket for a prompt, falling back to the exact
        size when the bucket would exceed the window (``Pb <= window``
        is the ring-safety bound: it keeps a bucket's pad tail off the
        prompt it pads).  The single definition of the bucketing rule —
        the batched (_flush_prefills) and sequential (_pad_bucket)
        admission paths must never desynchronize on it."""
        pb = 1 << (prompt_size - 1).bit_length()
        if pb > self._window:
            pb = prompt_size
        return pb

    def _pad_bucket(self, prompt: np.ndarray) -> jax.Array:
        """Zero-pad ``prompt`` to its pow-2 compile bucket (see
        :meth:`_prompt_bucket`)."""
        p = prompt.size
        padded = np.zeros(self._prompt_bucket(p), np.int32)
        padded[:p] = prompt
        return jnp.asarray(padded)

    def _harvest(self) -> None:
        for b in range(self._slots):
            if not (self._active[b] and self._done[b]):
                continue
            req = self._slot_req[b]
            s, pe = self._start[b], self._p_end[b]
            seq = self._slot_tokens(b)
            self.stats.generated_tokens += max(seq.size - (pe - s), 0)
            self.stats.completed += 1
            self._results[req.request_id] = seq
            self._active[b] = False
            self._unpin_slot_prefix(b)
            self._slot_req[b] = None
            wall = time.monotonic() - getattr(req, "t_admit", 0.0)
            if 0.0 < wall < 3600.0:
                self._avg_request_s = (0.8 * self._avg_request_s
                                       + 0.2 * wall
                                       if self._avg_request_s else wall)

    def _run_chunk(self) -> None:
        n = self._chunk       # ring: no window clamp (writes wrap)
        if self._queue:
            # Work is waiting: stop the chunk at the next KNOWN slot
            # retirement (its end bound — tick end[b]-2 finishes slot b)
            # so the freed slot refills immediately instead of idling to
            # the boundary.  eos stops stay unpredictable; this clamps
            # only on the exact bound.  The clamp is quantized DOWN to a
            # power of two: each distinct scan length is its own XLA
            # compile, so exact clamping could cost `chunk` compiles on
            # a cold cache — pow-2 sizes bound that at log2(chunk)+1
            # (undershooting just lands an extra boundary, never idles).
            live = self._active & ~self._done
            if live.any():
                nxt = int(self._end[live].min()) - 1 - self._tick
                if 0 < nxt < n:
                    n = 1 << (nxt.bit_length() - 1)
        self._rng, sub = jax.random.split(self._rng)
        try:
            # When no ACTIVE slot uses the prefix, run the plain program
            # (see _dispatch_args); both variants compile once.
            knobs, kp, vp = self._dispatch_args(
                bool(np.any(self._use_prefix & self._active)))
            self._tokens, self._kc, self._vc, done, busy = _chunk_program(
                n, knobs, self._params, self._tokens,
                self._kc, self._vc, jnp.asarray(self._start),
                jnp.asarray(self._p_end), jnp.asarray(self._end),
                jnp.asarray(self._done), jnp.asarray(self._active),
                jnp.asarray(self._temp), jnp.asarray(self._eos),
                jnp.asarray(self._use_prefix), kp, vp,
                jnp.int32(self._tick), sub)
            # The only per-chunk host pull: the [B] done vector (the
            # token buffer stays on device; harvest/partial pull rows).
            if self._replicate2 is not None:
                done, busy = self._replicate2(done, busy)
            self._done = np.array(done)
        except Exception:
            self._poisoned = True
            raise
        self._tick += n
        self.stats.ticks += n
        self.stats.busy_slot_ticks += int(busy)
        self.stats.chunks += 1
