"""Does the system start on the chip?  One process, the normal entry points.

    python chip_smoke.py          # on a machine with a TPU; no arguments

Drives, at the full width of the round-2 flagship LM (12 layers x 768,
sequences of 2,048, vocabulary 32,128, ``attn_fn`` left at its default):

* every Pallas kernel in the repo, compiled, against its reference;
* the trainer: ``AutoDist -> capture -> create_distributed_session ->
  sess.run`` for a few steps on one fixed host batch;
* the server: ``serve(paged=True)`` answering eight HTTP requests (six
  blocking, two streamed, two sharing a 512-token prefix), greedy tokens
  against ``make_generator`` in float32, then ``serve(paged=False)``;
* with four chips or more, the same trainer over ``{'data': 4}`` and
  ``{'data': 2, 'model': 2}`` and the paged engine over a 4-way ``model``
  axis, with the per-device shapes read from the compiled HLO.

It exits nonzero unless ``jax.devices()[0].platform`` is ``tpu``: there is
no switch that lets it pass on a CPU.  A phase that raises ends the run
with its traceback.  Each phase prints one line; the last line of stdout is
``{"ok": true, "device": {...}}``.  The times it prints are set-up
information (compilation, first step), not throughput: that is the
benchmark's job.  The work is in functions that take sizes, so
``tests/test_chip_smoke.py`` calls the same functions on the CPU mesh at a
tiny size.
"""
from __future__ import annotations

import functools
import gc
import http.client
import importlib.metadata
import json
import math
import re
import sys
import threading
import time

#: The round-2 flagship (ROADMAP S2): the one configuration with an
#: on-chip record, at its full width.  Vocabulary is the model's default.
FLAGSHIP_LM = dict(num_layers=12, num_heads=12, head_dim=64, d_ff=3072,
                   max_len=2048, seq_len=2048)
SEED = 0


# ---------------------------------------------------------------------------
# phase bookkeeping
# ---------------------------------------------------------------------------

class CompileWatch:
    """Seconds spent in XLA compilation (or fetching from the persistent
    cache) and the cache's hits and misses, from jax's own monitoring
    events, which also see the server's driver thread."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == self._COMPILE:
            with self._lock:
                self.compile_s += secs

    def _event(self, event, **_):
        with self._lock:
            self.hits += event == self._HIT
            self.misses += event == self._MISS

    def snapshot(self):
        with self._lock:
            return self.compile_s, self.hits, self.misses


def run_phase(watch: CompileWatch, name: str, fn, *args, **kwargs):
    """Run one phase and print its line.  Nothing is caught: a phase that
    raises ends the process with the traceback."""
    c0, h0, m0 = watch.snapshot()
    t0 = time.perf_counter()
    facts = fn(*args, **kwargs)
    wall = time.perf_counter() - t0
    c1, h1, m1 = watch.snapshot()
    print(f"phase {name}: ok compile_s={c1 - c0:.1f} "
          f"run_s={wall - (c1 - c0):.1f} cache_hits={h1 - h0} "
          f"cache_misses={m1 - m0} {json.dumps(facts)}", flush=True)
    return facts


def _rel_err(got, want) -> float:
    """max|got - want| over max|want|, in float32."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


# ---------------------------------------------------------------------------
# kernels (ISSUE 21 §6): every pl.pallas_call, compiled, against its reference
# ---------------------------------------------------------------------------

def check_flash(shape, dtype, causal: bool, *, interpret: bool, tol: float,
                ref_batch: int = 2) -> dict:
    """Flash attention forward, dQ and dK/dV at ``shape`` ([B, T, H, D], or
    [B, T, H, Dk, Dv] where values are narrower than keys) against dense
    attention in float32 on the first ``ref_batch`` rows (a batch dense
    attention can hold: it materializes [B, H, T, T])."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu.models.transformer import dense_attention
    from autodist_tpu.ops.flash_attention import flash_attention

    rng = np.random.RandomState(SEED)
    q, k = (rng.randn(*shape[:4]).astype(np.float32) * 0.5
            for _ in range(2))
    v, w = (rng.randn(*shape[:3], shape[-1]).astype(np.float32) * 0.5
            for _ in range(2))           # w: a fixed cotangent

    def out_and_grads(attn, q, k, v, w):
        out, pullback = jax.vjp(attn, q, k, v)
        return (out,) + pullback(w)

    flash = functools.partial(flash_attention, causal=causal,
                              interpret=interpret)
    dense = functools.partial(dense_attention, causal=causal)
    got = jax.jit(functools.partial(out_and_grads, flash))(
        *(jnp.asarray(x, dtype) for x in (q, k, v, w)))
    with jax.default_matmul_precision("highest"):   # a float32 reference
        # from the inputs as the kernel saw them (rounded to ``dtype``)
        want = jax.jit(functools.partial(out_and_grads, dense))(
            *(jnp.asarray(x[:ref_batch], dtype).astype(jnp.float32)
              for x in (q, k, v, w)))
    errs = {name: _rel_err(np.asarray(g, np.float32)[:ref_batch], r)
            for name, g, r in zip(("fwd", "dq", "dk", "dv"), got, want)}
    for name, err in errs.items():
        if not err <= tol:
            raise AssertionError(
                f"flash attention {name} at {shape} {dtype} "
                f"causal={causal}: relative error {err:.3g} > {tol}")
    return errs


def check_int8_matmul(m: int, k: int, n: int, *, interpret: bool) -> float:
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu.ops.quant import int8_matmul, quantize_weight

    rng = np.random.RandomState(SEED)
    x = jnp.asarray(rng.randn(m, k), jnp.bfloat16)
    w = quantize_weight(jnp.asarray(rng.randn(k, n) * 0.05, jnp.float32))
    got = int8_matmul(x, w, interpret=interpret)
    want = np.asarray(x, np.float32) @ (
        np.asarray(w.q, np.float32) * np.asarray(w.scale))
    err = _rel_err(got, want)
    if not err <= 1e-2:          # the output is rounded to bfloat16
        raise AssertionError(f"int8_matmul [{m},{k}]x[{k},{n}]: relative "
                             f"error {err:.3g} > 1e-2")
    return err


def check_fused_elementwise(n: int, *, interpret: bool) -> dict:
    """Detect, Adam update and the three quantized-hop kernels on a flat
    float32 bucket of ``n`` elements against their unfused lowerings
    (each side one jitted program; the comparison is on the host)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from autodist_tpu.kernel.synchronization import quant_ring as qr
    from autodist_tpu.ops import fused_kernels as fk

    rng = np.random.RandomState(SEED)
    facts = {}

    def host(tree):
        return jax.tree_util.tree_map(
            lambda a: np.asarray(a, np.float32), tree)

    # -- guard statistics: count exact, square sum to summation order
    x = rng.randn(n).astype(np.float32)
    x_bad = x.copy()
    bad = rng.choice(n, 5, replace=False)
    x_bad[bad[:3]], x_bad[bad[3:]] = np.nan, np.inf
    detect = jax.jit(functools.partial(fk.fused_detect_stats,
                                       interpret=interpret))
    nf, _ = detect(x_bad)
    _, sq = detect(x)
    if int(nf) != 5:
        raise AssertionError(f"fused detect counted {int(nf)} non-finite "
                             f"elements, 5 were planted")
    want_sq = float(np.sum(x.astype(np.float64) ** 2))
    facts["detect_sq_rel"] = abs(float(sq) - want_sq) / want_sq
    if not facts["detect_sq_rel"] <= 1e-5:
        raise AssertionError(f"fused detect square sum off by "
                             f"{facts['detect_sq_rel']:.3g} relative")

    # -- unscale/clip/Adam against optax.adam on mult * g
    spec = fk.AdamSpec(lr=1e-3)
    p, g, mu = (rng.randn(n).astype(np.float32) for _ in range(3))
    nu = rng.rand(n).astype(np.float32)
    count, mult = np.int32(3), np.float32(0.5)
    opt = optax.adam(spec.lr, b1=spec.b1, b2=spec.b2, eps=spec.eps)

    def unfused(p, g, mu, nu, count, mult):
        state = opt.init(p)
        state = (state[0]._replace(count=count, mu=mu, nu=nu),) + state[1:]
        upd, state = opt.update(mult * g, state, p)
        return p + upd, state[0].mu, state[0].nu

    got = host(jax.jit(lambda *a: fk.fused_adam_update(
        *a[:5], spec, mult=a[5], interpret=interpret))(
            p, g, mu, nu, count, mult))
    want = host(jax.jit(unfused)(p, g, mu, nu, count, mult))
    facts["adam_abs"] = max(float(np.abs(a - b).max())
                            for a, b in zip(got, want))
    if not facts["adam_abs"] <= 1e-6:    # the PR 5 ZeRO-1 contract
        raise AssertionError(f"fused Adam update off by "
                             f"{facts['adam_abs']:.3g} absolute")

    # -- quantize, hop-accumulate, dequantize-add.  Payloads are
    # dequantized on the host: inside one program XLA may skip the
    # rounding of a quantize -> dequantize pair (excess precision), which
    # would make the reference finer than the wire.  A fused payload may
    # differ from the unfused one by a grid step where x/scale lands on a
    # rounding boundary (two dividers), never by more.
    xq, chunk = (rng.randn(n).astype(np.float32) for _ in range(2))

    def deq(q, s):
        return (np.asarray(q, np.float32).reshape(len(s), -1)
                * np.asarray(s)[:, None]).reshape(-1)

    for fmt in (qr.WIRE_INT8, qr.WIRE_FP8_E4M3):
        kw = dict(fmt=fmt, interpret=interpret)
        quantize = jax.jit(functools.partial(qr.quantize_blocks, fmt=fmt))
        q_u, s_u, sat_u = quantize(xq)
        q_f, s_f, err_f, sat_f = jax.jit(
            functools.partial(fk.fused_quantize, **kw))(xq)
        if float(sat_f) != float(sat_u):
            raise AssertionError(f"fused quantize {fmt.name}: saturation "
                                 f"count {float(sat_f)} != {float(sat_u)}")
        acc = deq(q_u, s_u) + chunk
        q_h, s_h, _, _ = jax.jit(functools.partial(
            fk.fused_hop_accumulate, **kw))(q_u, s_u, chunk)
        # one grid step: the scale for int8; 32 scales at fp8's top binade
        step = float(np.max(s_u)) * (1.0 if fmt.name == "int8" else 32.0)
        off = {
            "quantize": (deq(q_f, s_f) - deq(q_u, s_u), 2 * step),
            "quantize_err": (np.asarray(err_f) - (xq - deq(q_f, s_f)),
                             1e-5),
            "dequant_add": (np.asarray(jax.jit(functools.partial(
                fk.fused_dequant_add, **kw))(q_u, s_u, chunk)) - acc, 1e-5),
            "hop": (deq(q_h, s_h) - deq(*quantize(acc)[:2]), 2 * step),
        }
        for name, (diff, bound) in off.items():
            worst = float(np.abs(diff).max())
            if not worst <= bound:
                raise AssertionError(f"fused {name} {fmt.name}: off by "
                                     f"{worst:.3g} > {bound:.3g}")
            facts[f"{name}_{fmt.name}"] = worst
    return facts


def check_paged_attention(slots: int, heads: int, head_dim: int,
                          block_size: int, blocks_per_slot: int, *,
                          interpret: bool) -> float:
    """The paged-attention kernel against gather-then-softmax (the
    lowering ``serving/paged_kv.py`` uses without it), bfloat16 pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu.ops.fused_kernels import paged_attention

    rng = np.random.RandomState(SEED)
    num_blocks = slots * blocks_per_slot + 1
    window = blocks_per_slot * block_size
    q = jnp.asarray(rng.randn(slots, heads, head_dim), jnp.bfloat16)
    kc, vc = (jnp.asarray(
        rng.randn(num_blocks, block_size, heads, head_dim) * 0.5,
        jnp.bfloat16) for _ in range(2))
    bt = (1 + rng.permutation(num_blocks - 1)).reshape(
        slots, blocks_per_slot).astype(np.int32)
    rel = rng.randint(0, window, slots).astype(np.int32)
    got = paged_attention(q, kc, vc, bt, rel, interpret=interpret)

    def reference(q, kc, vc, bt, rel):
        q, kc, vc = (x.astype(jnp.float32) for x in (q, kc, vc))
        kb = jnp.take(kc, bt, axis=0).reshape(slots, window, heads, head_dim)
        vb = jnp.take(vc, bt, axis=0).reshape(slots, window, heads, head_dim)
        logits = jnp.einsum("bhk,bwhk->bhw", q, kb,
                            precision="highest") / head_dim ** 0.5
        mask = jnp.arange(window)[None, None, :] <= rel[:, None, None]
        probs = jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1)
        return jnp.einsum("bhw,bwhk->bhk", probs, vb, precision="highest")

    err = _rel_err(got, jax.jit(reference)(q, kc, vc, bt, rel))
    if not err <= 1e-2:          # the output is rounded to bfloat16
        raise AssertionError(f"paged attention: relative error {err:.3g} "
                             f"> 1e-2")
    return err


def kernels_phase(*, flash, matmul_shapes, bucket_elems: int, paged: dict,
                  interpret: bool) -> dict:
    """``flash``: ``(shape, dtype, causal, tol)`` per configuration."""
    return {
        "flash": [check_flash(shape, dtype, causal, interpret=interpret,
                              tol=tol)
                  for shape, dtype, causal, tol in flash],
        "int8_matmul": [check_int8_matmul(*s, interpret=interpret)
                        for s in matmul_shapes],
        "fused": check_fused_elementwise(bucket_elems, interpret=interpret),
        "paged_attention": check_paged_attention(**paged,
                                                 interpret=interpret),
    }


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

def pallas_call_shapes(hlo_text: str):
    """Result shapes of every Mosaic custom call in compiled HLO text, one
    list of ``(dtype, dims)`` per call."""
    calls = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        result = line.split(" custom-call(")[0].split("=", 1)[1]
        calls.append([(dt, tuple(int(x) for x in dims.split(",") if x))
                      for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]",
                                                 result)])
    return calls


def memory_in_use(devices):
    """``bytes_in_use`` of each device, checked for even shares; None
    where the backend has no memory statistics (the CPU).  Every device
    must hold something, and the devices after the first (which also
    holds what the one-chip phases left) within 1.5x of each other."""
    stats = [d.memory_stats() for d in devices]
    if None in stats:
        return None
    held = [int(s["bytes_in_use"]) for s in stats]
    rest = held[1:] or held
    if min(held) <= 0 or max(rest) > 1.5 * min(rest):
        raise AssertionError(
            f"devices do not hold even shares: bytes_in_use {held}")
    return held


def make_lm(lm: dict, dtype):
    """``(spec, params)``: random weights from the seed (one jitted init
    rather than flax's op-by-op eager one)."""
    import jax

    from autodist_tpu.models.transformer_lm import transformer_lm

    spec = transformer_lm(**lm, dtype=dtype)
    return spec, jax.jit(spec.init)(jax.random.PRNGKey(SEED))


def open_session(spec, params, strategy: str, mesh_axes: dict, **capture):
    """``(AutoDist, session)`` the normal way in: ``capture`` under
    ``adamw(1e-3)``, then ``create_distributed_session`` on the first
    devices the mesh needs."""
    import jax
    import optax

    from autodist_tpu import strategy as strategies
    from autodist_tpu.autodist import (AutoDist,
                                       _reset_default_autodist_for_testing)
    from autodist_tpu.mesh import build_mesh

    devices = jax.devices()[:math.prod(mesh_axes.values())]
    _reset_default_autodist_for_testing()   # one AutoDist at a time
    ad = AutoDist(strategy_builder=getattr(strategies, strategy)(),
                  mesh_axes=mesh_axes)
    with ad.scope():
        ad.capture(params=params, optimizer=optax.adamw(1e-3),
                   loss_fn=spec.loss_fn, sparse_vars=spec.sparse_vars,
                   **capture)
    return ad, ad.create_distributed_session(
        mesh=build_mesh(mesh_axes, devices=devices))


def train_phase(spec, params, *, strategy: str, mesh_axes: dict,
                batch_size: int, steps: int) -> dict:
    """``steps`` calls of ``sess.run`` on one fixed host batch (placed by
    ``run``).  Every loss finite, the last lower than the first; on TPU
    devices the compiled step must hold the Pallas attention calls at the
    per-device batch and heads, and every device must hold state (on the
    CPU mesh the default attention is dense: no such call may appear)."""
    import jax
    import numpy as np

    from autodist_tpu.autodist import _reset_default_autodist_for_testing

    devices = jax.devices()[:math.prod(mesh_axes.values())]
    ad, sess = open_session(spec, params, strategy, mesh_axes)
    batch = spec.sample_batch(batch_size, seed=SEED)
    losses = [float(sess.run(batch)["loss"]) for _ in range(steps)]
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    facts = {"mesh": dict(sess.mesh.shape), "batch": batch_size,
             "losses": [round(x, 4) for x in losses]}

    per_batch = batch_size // mesh_axes.get("data", 1)
    per_heads = spec.config["num_heads"] // mesh_axes.get("model", 1)
    attn = [shapes for shapes in pallas_call_shapes(
        sess.lower_step(batch).compile().as_text())
        if any(len(dims) == 4 for _, dims in shapes)]
    if devices[0].platform == "tpu":
        if not attn:
            raise AssertionError(
                "no Pallas custom call in the compiled step: the default "
                "attention did not resolve to the flash kernel")
        for shapes in attn:     # kernel layout is [B, H, T, D]
            for _, dims in shapes:
                if dims[:2] != (per_batch, per_heads):
                    raise AssertionError(
                        f"attention custom call works on {dims}; per "
                        f"device it should lead with "
                        f"({per_batch}, {per_heads}): the kernel is not "
                        f"sharded over the mesh")
        facts["attention_calls"] = len(attn)
        facts["attention_call_dims"] = list(attn[0][0][1])
        facts["bytes_in_use"] = memory_in_use(devices)
    elif attn:
        raise AssertionError("unexpected Pallas call off the TPU")
    del sess, ad
    _reset_default_autodist_for_testing()
    gc.collect()
    return facts


def train_moe_phase(model: dict, *, batch_size: int, steps: int) -> dict:
    """``models/mla_moe_lm.py`` (latent attention, routed experts of which
    this chip holds a share) through ``capture(has_aux=True)``: ``steps``
    calls of ``sess.run`` on one fixed batch.  Every loss finite, the last
    lower than the first; the per-expert token counts come back with every
    step and add up to no more than every pick of every token; on a TPU the
    compiled step holds the Pallas attention calls one sequence at a time
    with keys wider than values, a forward and a backward a layer and no
    third: the layers' checkpoints keep ``o`` and ``lse`` by name, and the
    gauge says how many bytes that holds.  The kernel's q and k hold their
    rope columns DE-INTERLEAVED (the weights' even columns, then the odd
    ones; ``mla_moe_lm.attention_operands``): scores are unchanged, but a
    decode path or a latent cache must not assume interleaved pairs at the
    kernel's boundary."""
    import jax
    import numpy as np

    from autodist_tpu.autodist import _reset_default_autodist_for_testing
    from autodist_tpu.models.mla_moe_lm import mla_moe_lm

    spec = mla_moe_lm(**model, return_counts=True)
    ad, sess = open_session(
        spec, jax.jit(spec.init)(jax.random.PRNGKey(SEED)), "AllReduce",
        {"data": 1}, expert_vars=spec.expert_vars, has_aux=True)
    batch = spec.sample_batch(batch_size, seed=SEED)
    outs = [sess.run(batch) for _ in range(steps)]
    losses = [float(o["loss"]) for o in outs]
    if not np.all(np.isfinite(losses)) or not losses[-1] < losses[0]:
        raise AssertionError(f"loss not finite or did not fall: {losses}")
    cfg = spec.config
    layers = cfg["num_layers"] - cfg["first_dense"]
    picks = batch["tokens"].size * cfg["top_k"]
    counts = np.asarray(outs[-1]["aux"]["tokens_per_expert"])
    if counts.shape != (layers, cfg["experts_held"][1]) \
            or not 0 < counts.sum() <= layers * picks:
        raise AssertionError(f"tokens per expert: {counts.tolist()}")
    facts = {"losses": [round(x, 4) for x in losses],
             "tokens_per_expert": counts.tolist(),
             "share_of_picks_here": round(
                 float(counts.sum()) / (layers * picks), 4)}
    if jax.devices()[0].platform == "tpu":
        attn = [[dims for _, dims in shapes] for shapes in pallas_call_shapes(
            sess.lower_step(batch).compile().as_text())
            if any(len(dims) == 4 for _, dims in shapes)]
        widths = {dims[3] for call in attn for dims in call if len(dims) == 4}
        if not attn or not {cfg["qk_nope"] + cfg["qk_rope"],
                            cfg["v_head"]} <= widths:
            raise AssertionError(f"attention custom calls work on {attn}")
        if len(attn) != 2 * cfg["num_layers"]:
            raise AssertionError(
                f"{len(attn)} attention custom calls in {cfg['num_layers']} "
                f"layers: the rematerialised layer runs its forward kernel "
                f"again")
        facts["attention_calls"] = len(attn)
        facts["rope_columns_at_kernel"] = "de-interleaved, q and k alike"
        facts["bytes_in_use"] = memory_in_use(jax.devices()[:1])
    from autodist_tpu.telemetry.registry import DEFAULT_REGISTRY

    facts["remat_kept_bytes"] = {
        m.labels["name"]: int(m.value) for m in DEFAULT_REGISTRY.metrics()
        if m.name == "autodist_remat_kept_bytes_per_step"}
    facts["chunks"] = forced_router_takes_every_chunk(cfg)
    del sess, ad
    _reset_default_autodist_for_testing()
    gc.collect()
    return facts


def forced_router_takes_every_chunk(cfg: dict, tokens: int = 512) -> dict:
    """One routed layer at the model's widths over ``tokens`` tokens, twice:
    under the router as seeded (an even one: the first chunk of the sorted
    order holds its picks and the loop over further chunks makes no turn)
    and under a selection bias that sends every pick to the held experts
    (every chunk: every pick has its row).  Both against the layer written
    out plainly (every held expert over every token, weighted by the
    router's weights), within what a bfloat16 pass of three products
    leaves."""
    import jax
    import jax.numpy as jnp

    from autodist_tpu.parallel import moe

    first, count = cfg["experts_held"]
    total, top_k = cfg["num_experts"], cfg["top_k"]
    if count < top_k:
        return {"skipped": f"{count} held experts cannot take {top_k} picks"}
    params = moe.init_routed_moe_params(
        jax.random.PRNGKey(SEED), cfg["d_model"], cfg["d_expert"], total,
        experts_held=count)
    x = jax.random.normal(jax.random.PRNGKey(SEED + 1),
                          (tokens, cfg["d_model"]))
    biases = {"even": params["router_bias"],
              "forced": jnp.full((total,), -10.0).at[
                  first:first + count].set(10.0)}

    def plain(p, x):
        scores = jax.nn.sigmoid(jnp.dot(
            x, p["router"], precision=jax.lax.Precision.HIGHEST))
        _, chosen = jax.lax.top_k(scores + p["router_bias"], top_k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        gates = jnp.zeros_like(scores).at[
            jnp.arange(tokens)[:, None], chosen].set(
                picked / picked.sum(-1, keepdims=True))
        each = jax.vmap(lambda w: moe.swiglu(w, x))(p["experts"])
        return jnp.einsum("ne,end->nd", gates[:, first:first + count], each)

    def layer(p, x):
        return moe.routed_moe_ffn(p, x, top_k=top_k,
                                  experts_held=(first, count))

    def with_gradient(f):
        """``(y, its other result, the gradient of sum(y * x) by x)``: the
        cotangent of ``x`` comes back to token order as ``y`` does."""
        def probe(p, x):
            y, aux = f(p, x)
            return jnp.sum(y * jax.lax.stop_gradient(x)), (y, aux)
        return jax.jit(jax.grad(probe, argnums=1, has_aux=True))

    def gap(got, want):
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    facts = {}
    for name, bias in biases.items():
        p = dict(params, router_bias=bias)
        d_x, (y, counts) = with_gradient(layer)(p, x)
        want_d_x, (want, _) = with_gradient(lambda p, x: (plain(p, x), 0))(
            p, x)
        gaps = gap(y, want), gap(d_x, want_d_x)
        rungs, calls = moe.budgets_taken(counts, tokens * top_k, total)
        taken = rungs[int(jnp.argmax(calls))]
        if max(gaps) > 2e-2 or (name == "forced" and (
                taken != rungs[-1] or int(counts.sum()) != tokens * top_k)):
            raise AssertionError(
                f"{name} router: {int(counts.sum())} rows routed here took "
                f"chunks over {taken} of {rungs} places, value and gradient "
                f"{gaps} from the layer written out")
        facts["rungs"] = list(rungs)
        facts[name] = {"rows_routed_here": int(counts.sum()),
                       "rows_covered": taken, "gap": round(gaps[0], 6),
                       "gradient_gap": round(gaps[1], 6)}
    return facts


def expert_model_steps(spec, *, batch_size: int, steps: int, pairs: str):
    """``steps`` calls of ``sess.run`` on one fixed batch of a decoder with
    routed experts through ``capture(has_aux=True)``: every loss finite,
    the per-expert token counts back, and the gauge ``pairs`` (pairs of
    query and key its attention is ASKED for, the first kind, and pairs
    its kernels compute) in order.  Returns ``(ad, sess, batch, facts)``;
    the caller ends with :func:`close_session`."""
    import jax
    import numpy as np

    from autodist_tpu.telemetry.registry import DEFAULT_REGISTRY

    ad, sess = open_session(
        spec, jax.jit(spec.init)(jax.random.PRNGKey(SEED)), "AllReduce",
        {"data": 1}, expert_vars=spec.expert_vars, has_aux=True)
    batch = spec.sample_batch(batch_size, seed=SEED)
    outs = [sess.run(batch) for _ in range(steps)]
    losses = [float(o["loss"]) for o in outs]
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"loss not finite: {losses}")
    cfg = spec.config
    counts = np.asarray(outs[-1]["aux"]["tokens_per_expert"])
    picks = batch["tokens"].size * cfg["top_k"]
    # a model's leading dense layers have no experts and return no counts
    layers = cfg["num_layers"] - cfg.get("num_dense_layers", 0)
    if counts.shape != (layers, cfg["experts_held"][1]) \
            or not 0 < counts.sum() <= layers * picks:
        raise AssertionError(f"tokens per expert: {counts.tolist()}")
    found = {m.labels["kind"]: int(m.value)
             for m in DEFAULT_REGISTRY.metrics() if m.name == pairs}
    asked = found[next(k for k in found if k != "computed")]
    if not 0 < asked <= found["computed"]:
        raise AssertionError(f"pairs a step: {found}")
    return ad, sess, batch, {
        "losses": [round(x, 4) for x in losses],
        "tokens_per_expert": counts.tolist(), "pairs_per_step": found}


def close_session(ad, sess):
    from autodist_tpu.autodist import _reset_default_autodist_for_testing

    del sess, ad
    _reset_default_autodist_for_testing()
    gc.collect()


def check_dsa_select(t: int, heads: int, dim: int, topk: int, *,
                     block_k: int) -> dict:
    """``ops/index_select.py: dsa_select`` on one seeded sequence of ``t``
    tokens against the plain form it takes the place of on a TPU
    (``select_keys``; the kernel interpreted off a TPU): the words equal, or
    how many bits differ and where.
    On a chip both form ``highest``'s six bfloat16 products, the kernel two
    to a pass and XLA one, so their float32 sums may differ in a last bit
    and a pick AT a row's threshold change places with the next; anything
    else is a fault: every row takes ``min(its keys, topk)`` keys, no key
    after the query, and fewer than one pick in a thousand moves."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu.models.gqa_dsa_moe_lm import select_keys
    from autodist_tpu.ops.flash_attention import unpack_selection
    from autodist_tpu.ops.index_select import dsa_select
    from autodist_tpu.ops.pallas_utils import pick_block

    rng = np.random.RandomState(SEED)
    qi = jnp.asarray(rng.randn(t, heads, dim), jnp.float32)
    ki = jnp.asarray(rng.randn(t, dim), jnp.float32)
    w = jnp.asarray(rng.randn(t, heads) * dim ** -0.5, jnp.float32)
    bk = pick_block(t, block_k)
    want = jax.jit(functools.partial(select_keys, topk=topk, rows=bk,
                                     block_k=bk))(qi, ki, w)
    words, ties = dsa_select(qi, ki, w, topk=topk, block_k=bk)
    got = np.asarray(unpack_selection(words, block_k=bk))
    moved = got != np.asarray(unpack_selection(want, block_k=bk))
    facts = {"bits_differing": int(moved.sum()),
             "rows_differing": int(moved.any(axis=1).sum()),
             "first_places": np.argwhere(moved)[:8].tolist(),
             "tiles_searched_twice": int(np.asarray(ties).sum())}
    taken = np.minimum(np.arange(t) + 1, topk)
    if (got.sum(axis=1) != taken).any() or np.triu(got, 1).any():
        raise AssertionError(f"dsa_select at T={t}: a row takes another "
                             f"number of keys than {topk}, or a later key")
    if facts["bits_differing"] > 2e-3 * taken.sum():
        raise AssertionError(f"dsa_select against select_keys: {facts}")
    return facts


def train_dsa_moe_phase(model: dict, *, batch_size: int, steps: int) -> dict:
    """``models/gqa_dsa_moe_lm.py`` (grouped-query heads over the keys a
    learned indexer selects, softmax-routed experts of which this chip
    holds a share) through ``capture(has_aux=True)``: ``steps`` calls of
    ``sess.run`` on one fixed batch.  Every loss finite; the per-expert
    token counts come back; the gauges say how many pairs the attention
    was asked for and how many its kernel scores; on a TPU the compiled
    step holds a forward and a backward Pallas call a layer, none run
    twice, and the forward's result has the QUERY heads while its keys
    went in with their own (``num_kv_heads``), and one ``dsa_select`` call
    a layer selects the keys; that kernel's words on one seeded sequence
    of the model's length against the plain form's
    (:func:`check_dsa_select`)."""
    import jax

    from autodist_tpu.models.gqa_dsa_moe_lm import gqa_dsa_moe_lm

    spec = gqa_dsa_moe_lm(**model, return_counts=True)
    ad, sess, batch, facts = expert_model_steps(
        spec, batch_size=batch_size, steps=steps,
        pairs="autodist_dsa_pairs_per_step")
    cfg = spec.config
    if jax.devices()[0].platform == "tpu":
        text = sess.lower_step(batch).compile().as_text()
        attn = [[dims for _, dims in shapes]
                for shapes in pallas_call_shapes(text)
                if any(len(dims) == 4 for _, dims in shapes)]
        heads = {dims[1] for call in attn for dims in call if len(dims) == 4}
        if len(attn) != 2 * cfg["num_layers"] \
                or heads != {cfg["num_heads"]}:
            raise AssertionError(f"attention custom calls work on {attn}")
        keys_with_their_own_heads(text, cfg)
        selects = len(re.findall(
            r"^\s*(?:ROOT )?%dsa_select[.\d]* = .*tpu_custom_call", text,
            re.M))
        if cfg["seq_len"] > cfg["topk"] and selects != cfg["num_layers"]:
            raise AssertionError(f"{selects} dsa_select calls in the step")
        facts["attention_calls"] = len(attn)
        facts["bytes_in_use"] = memory_in_use(jax.devices()[:1])
    close_session(ad, sess)
    facts["select_against_the_plain_form"] = check_dsa_select(
        cfg["seq_len"], cfg["index_heads"], cfg["index_dim"], cfg["topk"],
        block_k=model.get("block_k", 512))
    return facts


def keys_with_their_own_heads(compiled_text: str, cfg: dict) -> None:
    kv = f"f32[1,{cfg['num_kv_heads']},{cfg['seq_len']},"
    if kv not in compiled_text:
        raise AssertionError(f"no kernel operand {kv}...]: the keys were "
                             f"repeated for their query heads")


def check_windowed_rows(shape, window, *, interpret: bool, tol: float,
                        rows: int = 256, **blocks) -> dict:
    """Flash attention at ``shape`` ([T, H, Hkv, D]: one sequence, grouped
    heads), causal, under ``window`` (None: every earlier key), forward and
    gradient, against the plain masked softmax ON A BLOCK OF ROWS: the loss
    reads the last ``rows`` query rows alone, so dQ, dK and dV of the whole
    call are those rows' and the plain formula forms ``rows x T`` scores,
    not ``T x T``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu.ops.flash_attention import flash_attention

    t, h, g, d = shape
    rng = np.random.RandomState(SEED)
    q = jnp.asarray(rng.randn(1, t, h, d) * 0.5, jnp.float32)
    k, v = (jnp.asarray(rng.randn(1, t, g, d) * 0.5, jnp.float32)
            for _ in range(2))
    w = jnp.asarray(rng.randn(1, rows, h, d) * 0.5, jnp.float32)
    pos = np.arange(t - rows, t)
    keep = np.arange(t)[None, :] <= pos[:, None]
    if window is not None:
        keep &= np.arange(t)[None, :] > pos[:, None] - window

    def kernel(q, k, v):
        o = flash_attention(q, k, v, True, interpret=interpret,
                            window=window, **blocks)
        return jnp.sum(o[:, -rows:] * w)

    def plain(q, k, v):
        kk, vv = (jnp.repeat(x, h // g, axis=2) for x in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, -rows:], kk) / d ** 0.5
        p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bkhd->bqhd", p, vv) * w)

    got = jax.jit(jax.value_and_grad(kernel, (0, 1, 2)))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(plain, (0, 1, 2)))(q, k, v)
    errs = {"value": abs(float(got[0]) - float(want[0]))
            / max(abs(float(want[0])), 1e-30)}
    errs.update({name: _rel_err(a, b) for name, a, b in
                 zip(("dq", "dk", "dv"), got[1], want[1])})
    for name, err in errs.items():
        if not err <= tol:
            raise AssertionError(
                f"flash attention {name} at {shape} window={window}: "
                f"relative error {err:.3g} > {tol}")
    return errs


def train_swa_moe_phase(model: dict, *, batch_size: int, steps: int,
                        tol: float, **blocks) -> dict:
    """``models/swa_moe_lm.py`` with ONE GLOBAL AND ONE WINDOW LAYER
    (every earlier key and no rotary; rotary and the window), a router that
    reads the layer's input and ReLU-gated experts, through
    ``capture(has_aux=True)``: ``steps`` calls of ``sess.run`` on one fixed
    batch, every loss finite, the per-expert token counts back, the gauges
    of the pairs attended and computed.  Before it, the two kinds'
    attention calls at the model's own shape, forward and gradient,
    against the plain masked softmax on a block of rows.  On a TPU the
    compiled step's Pallas calls are counted BY NAME: a forward and a
    backward ``global_attn`` and ``window_attn`` each, none run twice, the
    keys with their own heads."""
    import jax

    from autodist_tpu.models.swa_moe_lm import swa_moe_lm

    spec = swa_moe_lm(**model, return_counts=True)
    cfg = spec.config
    if tuple(cfg["window_layout"]) != (0, 1):
        raise AssertionError("one global and one window layer are asked")
    interpret = jax.devices()[0].platform != "tpu"
    shape = (cfg["seq_len"], cfg["num_heads"], cfg["num_kv_heads"],
             cfg["head_dim"])
    facts = {"rows_against_the_plain_formula": {
        name: check_windowed_rows(shape, window, interpret=interpret,
                                  tol=tol, rows=min(256, cfg["seq_len"]),
                                  **blocks)
        for name, window in (("global_attn", None),
                             ("window_attn", cfg["window"]))}}
    ad, sess, batch, stepped = expert_model_steps(
        spec, batch_size=batch_size, steps=steps,
        pairs="autodist_swa_pairs_per_step")
    facts.update(stepped)
    if not interpret:
        text = sess.lower_step(batch).compile().as_text()
        calls = {name: len(re.findall(
            rf"%{name}[\w.\-]* = .*custom_call_target=\"tpu_custom_call\"",
            text)) for name in ("global_attn", "window_attn")}
        print(f"  attention kernels of the step by name: {calls}",
              flush=True)
        if calls != {"global_attn": 2, "window_attn": 2}:
            raise AssertionError(f"attention custom calls: {calls}")
        keys_with_their_own_heads(text, cfg)
        facts["attention_calls"] = calls
        facts["bytes_in_use"] = memory_in_use(jax.devices()[:1])
    close_session(ad, sess)
    return facts


#: the worst of o and the five gradients against the recurrence on the chip
#: when ``_prepare``'s batched lines were XLA's (PERF.md section 6, PR 40)
PR40_WORST = {"default": 3.4e-3, "highest": 5.8e-7}


def check_gated_delta_rule(shape, *, chunk: int, interpret: bool, tol: float,
                            grad_tokens: int = 1024) -> dict:
    """``ops/gated_delta_rule.py`` (the chunked form: on a TPU its two
    kernels, which form a chunk's state-free blocks in VMEM, PR 49) against
    the recurrence token by token, ``shape = (T, Hk, Hv, D)`` one
    sequence: the forward over all ``T`` tokens, every input's gradient
    over the first ``grad_tokens`` (the recurrence's backward holds a state
    a token).  Twice: at jax's default precision (on a TPU the chunked
    form's products take bfloat16 operands: within ``tol``) and at
    ``highest`` (the same numbers: within 1e-4), printed beside what the
    chunked form with XLA's batched lines around a scan kernel read on the
    chip when it was built (PR 40).  Decays as the model makes them, 0.25
    to 15.75 times a softplus."""
    import jax
    import jax.numpy as jnp

    from autodist_tpu.models.gdn_moe_lm import decay_offsets, l2norm
    from autodist_tpu.ops.gated_delta_rule import (gated_delta_rule,
                                                   recurrence)

    t, hk, hv, d = shape
    ks = jax.random.split(jax.random.PRNGKey(SEED), 6)
    q = l2norm(jax.random.normal(ks[0], (1, t, hk, d))) / math.sqrt(d)
    k = l2norm(jax.random.normal(ks[1], (1, t, hk, d)))
    v = jax.random.normal(ks[2], (1, t, hv, d))
    g = -jnp.exp(decay_offsets(hv)) * jax.nn.softplus(
        jax.random.normal(ks[3], (1, t, hv)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, t, hv)))
    ct = jax.random.normal(ks[5], (1, t, hv, d))
    operands = (q, k, v, g, beta)
    short = tuple(x[:, :min(t, grad_tokens)] for x in operands + (ct,))

    def chunked(*a):
        return gated_delta_rule(*a, chunk=chunk, interpret=interpret)

    def gradients(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * short[-1]),
                                argnums=(0, 1, 2, 3, 4)))(*short[:-1])

    with jax.default_matmul_precision("highest"):
        want = jax.jit(recurrence)(*operands)
        want_grads = gradients(recurrence)
    facts = {}
    for precision, limit in (("default", tol), ("highest", 1e-4)):
        with jax.default_matmul_precision(precision):
            errs = [_rel_err(jax.jit(chunked)(*operands), want)] + [
                _rel_err(a, b) for a, b in zip(gradients(chunked),
                                               want_grads)]
        if not max(errs) <= limit:
            raise AssertionError(
                f"gated delta rule at {precision} precision, o and the "
                f"gradients of q, k, v, g, beta: {errs} > {limit}")
        facts[precision] = [float(f"{e:.3g}") for e in errs]
        print(f"  gated delta rule against the recurrence at {precision} "
              f"precision, o and the gradients of q, k, v, g, beta: "
              f"{facts[precision]} (PR 40 read {PR40_WORST[precision]:g} at "
              f"worst)", flush=True)
    return facts


def check_gdn_conv(shape, *, interpret: bool, tol: float = 1e-5) -> dict:
    """``ops/gdn_conv.py``'s kernel pair against the plain lines
    (``models/gdn_moe_lm.py: conv_qkvz``) at ``shape = (T, Hk, Hv, D)``, one
    sequence: q, k, v, z and, under seeded cotangents, the cotangent of
    ``qkvz`` and the three taps' gradients, each within ``tol`` of the plain
    form's largest (no product in either: float32 elementwise arithmetic,
    ``exp`` and ``rsqrt`` as Mosaic and as XLA lower them)."""
    import jax

    from autodist_tpu.models.gdn_moe_lm import conv_qkvz
    from autodist_tpu.ops.gdn_conv import conv_silu_l2norm

    t, hk, hv, d = shape
    share = hv // hk
    ks = jax.random.split(jax.random.PRNGKey(SEED), 8)
    qkvz = jax.random.normal(ks[0], (1, t, hk, 2 * d * (1 + share)))
    taps = tuple(jax.random.normal(k, (hk, n, 4)) * 0.5
                 for k, n in zip(ks[1:4], (d, d, share * d)))
    cts = tuple(jax.random.normal(k, (1, t, heads, d))
                for k, heads in zip(ks[4:], (hk, hk, hv, hv)))

    def both(fn):
        out, pull = jax.vjp(lambda x, *w: fn(x, *w, d ** -0.5), qkvz, *taps)
        return out + pull(cts)

    got = jax.jit(lambda: both(functools.partial(
        conv_silu_l2norm, interpret=interpret)))()
    errs = [_rel_err(a, b) for a, b in zip(got, jax.jit(
        lambda: both(conv_qkvz))())]
    if not max(errs) <= tol:
        raise AssertionError(
            f"gdn_conv against the plain lines, q, k, v, z and the "
            f"gradients of qkvz and the three taps: {errs} > {tol}")
    return {"rel_err": [float(f"{e:.3g}") for e in errs]}


def train_gdn_moe_phase(model: dict, *, batch_size: int, steps: int,
                        tol: float) -> dict:
    """``models/gdn_moe_lm.py`` with ONE LINEAR AND ONE FULL LAYER (the
    gated delta rule; gated attention with the rotary on part of a head),
    softmax-routed experts beside a gated shared expert, through
    ``capture(has_aux=True)``: ``steps`` calls of ``sess.run`` on one fixed
    batch, every loss finite, the per-expert token counts back, the gauges
    of the recurrence's FLOPs as written and as computed.  Before it, the
    recurrence's chunked form and kernel at the model's own shape against
    the recurrence token by token (:func:`check_gated_delta_rule`), and the
    convolution's kernel pair against the plain lines
    (:func:`check_gdn_conv`).  On a TPU the compiled step's Pallas calls
    are counted BY NAME: one ``gdn_scan`` (the forward's), one
    ``gdn_scan_bwd`` (the backward's, which runs its segments forward
    itself) and a forward and a
    backward ``gated_attn``, none run twice; TWO ``gdn_conv`` (the forward's
    and, the layer's checkpoint keeping nothing of it, the backward's
    recomputation) and one ``gdn_conv_bwd``."""
    import jax

    from autodist_tpu.models.gdn_moe_lm import gdn_moe_lm

    spec = gdn_moe_lm(**model, return_counts=True)
    cfg = spec.config
    if (cfg["num_layers"], cfg["full_interval"]) != (2, 2):
        raise AssertionError("one linear and one full layer are asked")
    interpret = jax.devices()[0].platform != "tpu"
    shape = (cfg["seq_len"], cfg["linear_key_heads"],
             cfg["linear_value_heads"], cfg["linear_head_dim"])
    facts = {"recurrence_against_token_by_token": check_gated_delta_rule(
        shape, chunk=cfg["chunk"], interpret=interpret, tol=tol),
        "conv_against_the_plain_lines": check_gdn_conv(
            shape, interpret=interpret)}
    ad, sess, batch, stepped = expert_model_steps(
        spec, batch_size=batch_size, steps=steps,
        pairs="autodist_gdn_flops_per_step")
    facts.update(stepped)
    if not interpret:
        text = sess.lower_step(batch).compile().as_text()
        calls = {name: len(re.findall(
            rf"%{name}[.\d]* = .*custom_call_target=\"tpu_custom_call\"",
            text)) for name in ("gdn_scan", "gdn_scan_bwd", "gated_attn",
                                "gdn_conv", "gdn_conv_bwd")}
        print(f"  kernels of the step by name: {calls}", flush=True)
        if calls != {"gdn_scan": 1, "gdn_scan_bwd": 1, "gated_attn": 2,
                     "gdn_conv": 2, "gdn_conv_bwd": 1}:
            raise AssertionError(f"custom calls: {calls}")
        facts["kernel_calls"] = calls
        facts["bytes_in_use"] = memory_in_use(jax.devices()[:1])
    close_session(ad, sess)
    return facts


def check_gated_short_conv(shape, *, tol: float) -> dict:
    """``models/sconv_moe_lm.py: gated_short_conv`` at ``shape = (T, D)``,
    one sequence, against the same sum written with ``jnp.roll`` and a
    mask: value and both gradients (no product in it: the chip's float32
    elementwise arithmetic against itself in another order)."""
    import jax
    import jax.numpy as jnp

    from autodist_tpu.models.sconv_moe_lm import gated_short_conv

    t, d = shape
    ks = jax.random.split(jax.random.PRNGKey(SEED), 3)
    bcx = jax.random.normal(ks[0], (1, t, 3 * d))
    taps = jax.random.normal(ks[1], (d, 3))
    ct = jax.random.normal(ks[2], (1, t, d))

    def rolled(bcx, taps):
        b, c, x = jnp.split(bcx, 3, axis=-1)
        z, rows = b * x, jnp.arange(t)[None, :, None]
        return c * sum(jnp.where(rows >= lag, jnp.roll(z, lag, axis=1), 0.0)
                       * taps[:, 2 - lag] for lag in range(3))

    def out_and_grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * ct), argnums=(0, 1)))(bcx, taps)

    (got, got_grads), (want, want_grads) = (out_and_grads(gated_short_conv),
                                            out_and_grads(rolled))
    errs = [abs(float(got - want)) / abs(float(want))] + [
        _rel_err(a, b) for a, b in zip(got_grads, want_grads)]
    if not max(errs) <= tol:
        raise AssertionError(f"gated short convolution, the sum and the "
                             f"gradients of bcx and the taps: {errs} > {tol}")
    return {"gaps": [float(f"{e:.3g}") for e in errs]}


def train_sconv_moe_phase(model: dict, *, batch_size: int, steps: int,
                          tol: float) -> dict:
    """``models/sconv_moe_lm.py`` with ONE DENSE CONV LAYER AND ONE
    ATTENTION EXPERT LAYER (the gated short convolution and a dense FFN;
    grouped-query attention with per-head norms and sigmoid-routed experts
    with a selection bias; a tied head), through
    ``capture(has_aux=True)``: ``steps`` calls of ``sess.run`` on one fixed
    batch, every loss finite, the one expert layer's token counts back, the
    gauges of the causal pairs asked for and computed.  Before it, the
    gated convolution at the model's own shape against the same sum in
    another form (:func:`check_gated_short_conv`).  On a TPU the compiled
    step's Pallas calls are counted BY NAME: a forward and a backward
    ``gqa_attn``, neither run twice."""
    import jax

    from autodist_tpu.models.sconv_moe_lm import sconv_moe_lm

    spec = sconv_moe_lm(**model, return_counts=True)
    cfg = spec.config
    if (cfg["layer_types"], cfg["num_dense_layers"]) != (
            ("conv", "full_attention"), 1) or not cfg["tie_embedding"]:
        raise AssertionError("one dense conv layer, one attention expert "
                             "layer and a tied head are asked")
    facts = {"gated_conv_against_rolled_sum": check_gated_short_conv(
        (cfg["seq_len"], cfg["d_model"]), tol=tol)}
    ad, sess, batch, stepped = expert_model_steps(
        spec, batch_size=batch_size, steps=steps,
        pairs="autodist_gqa_pairs_per_step")
    facts.update(stepped)
    if jax.devices()[0].platform == "tpu":
        text = sess.lower_step(batch).compile().as_text()
        calls = len(re.findall(
            r"%gqa_attn[.\d]* = .*custom_call_target=\"tpu_custom_call\"",
            text))
        print(f"  gqa_attn kernels of the step: {calls}", flush=True)
        if calls != 2:
            raise AssertionError(f"gqa_attn custom calls: {calls}")
        facts["kernel_calls"] = calls
        facts["bytes_in_use"] = memory_in_use(jax.devices()[:1])
    close_session(ad, sess)
    return facts


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

def _post(addr, body: dict, timeout: float = 900.0):
    conn = http.client.HTTPConnection(*addr, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if not body.get("stream"):
            return resp.status, json.loads(resp.read())
        # SSE: deltas must concatenate to the final event's new_tokens.
        deltas, final = [], None
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            event = json.loads(line[len("data: "):])
            if event.get("done"):
                final = event
                break
            deltas.extend(event.get("new_tokens", []))
        if final is None or "new_tokens" not in final:
            raise AssertionError(f"stream ended without a result: {final}")
        if deltas != final["new_tokens"][:len(deltas)]:
            raise AssertionError("streamed deltas disagree with the result")
        return resp.status, final
    finally:
        conn.close()


def _get_stats(addr) -> dict:
    conn = http.client.HTTPConnection(*addr, timeout=60)
    try:
        conn.request("GET", "/v1/stats")
        resp = conn.getresponse()
        if resp.status != 200:
            raise AssertionError(f"/v1/stats answered {resp.status}")
        return json.loads(resp.read())
    finally:
        conn.close()


def send_wave(addr, requests):
    """Send ``requests`` at once from client threads in this process;
    return their bodies in order.  Every answer must be 200 with exactly
    the requested number of new tokens after the prompt."""
    out = [None] * len(requests)

    def issue(i, req):
        try:
            out[i] = _post(addr, {"prompt_tokens": req["prompt"],
                                  "max_new_tokens": req["n"],
                                  "stream": req.get("stream", False)})
        except BaseException as e:   # re-raised below, in the main thread
            out[i] = e

    threads = [threading.Thread(target=issue, args=(i, r))
               for i, r in enumerate(requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    bodies = []
    for req, res in zip(requests, out):
        if isinstance(res, BaseException):
            raise res
        status, body = res
        if status != 200:
            raise AssertionError(f"HTTP {status}: {body}")
        if (len(body["new_tokens"]) != req["n"]
                or body["tokens"][:len(req["prompt"])] != req["prompt"]):
            raise AssertionError(
                f"asked for {req['n']} tokens after a prompt of "
                f"{len(req['prompt'])}, got {len(body['new_tokens'])}")
        bodies.append(body)
    return bodies


def paged_requests(vocab: int, sizes: dict):
    """The eight requests of the paged phase, in waves.  ``sizes`` gives
    the short prompt ``p``, the shared prefix length, and the lengths of
    the long prompts and of the answers."""
    import numpy as np

    rng = np.random.RandomState(SEED)

    def toks(n):
        return rng.randint(0, vocab, n).tolist()

    short, other = toks(sizes["p"]), toks(sizes["p"])
    prefix = toks(sizes["prefix"])
    n = sizes["n"]
    twin = {"prompt": short, "n": n[0]}
    return [
        [twin],                      # cold
        [dict(twin)],                # same prompt: its first block is cached
        [dict(twin)],                # and again, under identical conditions
        [{"prompt": prefix + toks(sizes["tails"][0]), "n": n[1]},
         {"prompt": toks(sizes["long"]), "n": n[2]},
         {"prompt": toks(sizes["mid"]), "n": n[3], "stream": True},
         {"prompt": other, "n": n[0]}],
        [{"prompt": prefix + toks(sizes["tails"][1]), "n": n[3],
          "stream": True}],          # shares the cached prefix
    ]


def serve_paged_phase(spec, params, *, sizes: dict, engine: dict,
                      mesh=None) -> dict:
    """``serve(paged=True)``: the waves of :func:`paged_requests` over
    HTTP, then ``/v1/stats``, then ``close()``."""
    from autodist_tpu.serving.server import serve

    waves = paged_requests(spec.config["vocab_size"], sizes)
    kwargs = dict(engine, mesh=mesh) if mesh is not None else engine
    srv = serve(spec, params, port=0, paged=True, **kwargs)
    try:
        bodies = [send_wave(srv.address, wave) for wave in waves]
        stats = _get_stats(srv.address)
        eng = srv.engine
        # Two identical requests under identical conditions (both find the
        # first block cached, both alone in the engine): identical tokens.
        if bodies[1][0]["tokens"] != bodies[2][0]["tokens"]:
            raise AssertionError("identical requests gave different tokens")
        n_requests = sum(len(w) for w in waves)
        if stats["requests_served"] != n_requests or stats["outstanding"]:
            raise AssertionError(f"stats disagree with {n_requests} "
                                 f"served requests: {stats}")
        shared = sizes["prefix"] // engine["block_size"]
        if eng.trie.stats.hit_blocks < shared:
            raise AssertionError(
                f"prefix trie hit {eng.trie.stats.hit_blocks} blocks; the "
                f"shared prefix alone is {shared}")
        eng.assert_no_leaks()        # drained; runs BlockPool.verify()
        return {
            "requests": n_requests,
            "cold_equals_cached": bodies[0][0]["tokens"]
            == bodies[1][0]["tokens"],
            "trie_hit_blocks": eng.trie.stats.hit_blocks,
            "prefix_hit_rate": stats["prefix_hit_rate"],
            "prefill_dispatches": stats["prefill_dispatches"],
            "chunks": stats["chunks"],
            "bytes_in_use": None if mesh is None else memory_in_use(
                mesh.devices.flat),
        }
    finally:
        srv.close()


#: How far below the reference's best logit a served token's logit may lie
#: when the token differs from the reference's greedy choice: the two
#: programs multiply float32 at the TPU's default (bfloat16-pass)
#: precision in different shapes, so near-ties can resolve differently.
LOGIT_TOLERANCE = 0.05


def serve_exact_phase(spec, params, *, sizes: dict, engine: dict) -> dict:
    """Float32 parameters: two requests through ``serve(paged=True)``
    against ``make_generator``'s greedy tokens, the equality the CPU
    tests assert.  Where a token differs, every served token must still
    be within :data:`LOGIT_TOLERANCE` of the best logit of a
    teacher-forced reference pass over the served sequence."""
    import numpy as np

    from autodist_tpu.models.generate import make_generator
    from autodist_tpu.serving.server import serve

    rng = np.random.RandomState(SEED + 1)
    p, n = sizes["p"], sizes["n"][0]
    requests = [{"prompt": rng.randint(0, spec.config["vocab_size"],
                                       p).tolist(), "n": n}
                for _ in range(2)]
    srv = serve(spec, params, port=0, paged=True, **engine)
    try:
        served = [send_wave(srv.address, [r])[0]["tokens"] for r in requests]
    finally:
        srv.close()
    gen = make_generator(spec)
    exact, worst_gap = 0, 0.0
    for req, tokens in zip(requests, served):
        want = np.asarray(gen(params, np.asarray([req["prompt"]], np.int32),
                              n))[0].tolist()
        if tokens == want:
            exact += 1
            continue
        _, logits = gen.with_logits(
            params, np.asarray([tokens], np.int32), 1)
        logits = np.asarray(logits[:, 0], np.float32)   # [P+N, V]
        for pos in range(p, p + n):   # logits[pos-1] chooses token pos
            row = logits[pos - 1]
            worst_gap = max(worst_gap, float(row.max() - row[tokens[pos]]))
        if not worst_gap <= LOGIT_TOLERANCE:
            raise AssertionError(
                f"a served token lies {worst_gap:.3g} below the reference's "
                f"best logit (tolerance {LOGIT_TOLERANCE})")
    return {"token_exact": exact, "of": len(requests),
            "worst_logit_gap": round(worst_gap, 5)}


def serve_slots_phase(spec, params, *, sizes: dict, engine: dict) -> dict:
    """Two requests through ``serve()``'s default slot engine."""
    import numpy as np

    from autodist_tpu.serving.server import serve

    rng = np.random.RandomState(SEED + 2)
    vocab = spec.config["vocab_size"]
    requests = [
        {"prompt": rng.randint(0, vocab, sizes["p"]).tolist(),
         "n": sizes["n"][0]},
        {"prompt": rng.randint(0, vocab, sizes["mid"]).tolist(),
         "n": sizes["n"][1], "stream": True},
    ]
    srv = serve(spec, params, port=0, paged=False, **engine)
    try:
        send_wave(srv.address, requests)
        stats = _get_stats(srv.address)
    finally:
        srv.close()
    if stats["requests_served"] != 2 or stats["engine_failed"]:
        raise AssertionError(f"slot engine stats: {stats}")
    return {"requests": 2, "completed": stats["completed"]}


# ---------------------------------------------------------------------------
# the run at full width
# ---------------------------------------------------------------------------

FULL_KERNELS = dict(
    # the training shape, the full (non-causal) mask in float32,
    # and latent attention's widths (keys 192, values 128), one sequence
    flash=[((8, 2048, 12, 64), "bfloat16", True, 3e-2),
           ((2, 512, 4, 64), "float32", False, 2e-2),
           ((1, 4096, 32, 192, 128), "float32", True, 2e-2)],
    matmul_shapes=[(8, 768, 3072), (8 * 1024, 768, 3072)],
    bucket_elems=1 << 20,            # a 4 MiB float32 bucket
    paged=dict(slots=8, heads=12, head_dim=64, block_size=32,
               blocks_per_slot=64))
# kanana-2-30b-a3b's widths (benchmark/configs/kanana-2-30b-a3b.ep8-share
# .json), the leading dense layer and one expert layer, 16 of 128 experts
FULL_MLA_MOE = dict(vocab_size=16032, num_layers=2, experts_held=(0, 16),
                    seq_len=2048, xent_chunk=5376)
# Keye-VL-2.0-30B-A3B's language model at its published widths (benchmark/
# configs/keye-vl-2.0-30b-a3b.ep8-share.json), one layer, 16 of 128
# experts, twice the 2,048 keys a row may select
FULL_DSA_MOE = dict(vocab_size=18992, num_layers=1, experts_held=(0, 16),
                    seq_len=4096, xent_chunk=6400)
# SmallThinker-21BA3B-Instruct at its published widths (benchmark/configs/
# smallthinker-21b-a3b.ep8-share.json): one global and one window layer,
# 8 of 64 experts, all 16,384 positions
FULL_SWA_MOE = dict(vocab_size=18992, num_layers=2, window_layout=(0, 1),
                    rope_layout=(0, 1), experts_held=(0, 8), seq_len=16384,
                    xent_chunk=6400)
# Qwen3-Next-80B-A3B-Instruct at its published widths (benchmark/configs/
# qwen3-next-80b-a3b.ep16-share.json): one linear and one full layer, 32 of
# 512 experts, 8,192 positions
FULL_GDN_MOE = dict(vocab_size=18992, num_layers=2, full_interval=2,
                    experts_held=(0, 32), seq_len=8192, xent_chunk=6400)
FULL_SCONV_MOE = dict(layer_types=("conv", "full_attention"),
                      num_dense_layers=1, experts_held=(0, 8), seq_len=8192,
                      xent_chunk=4096)
FULL_SIZES = dict(p=64, prefix=512, tails=(40, 100), long=1024, mid=333,
                  n=(32, 48, 96, 128))
FULL_ENGINE = dict(slots=8, window=2048, block_size=32, chunk=16)


def main() -> int:
    from autodist_tpu.utils.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()    # before the first use of JAX
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but jax.devices()[0] is "
              f"{dev.platform!r} ({dev.device_kind}); nothing was run",
              file=sys.stderr)
        return 1
    import jax.numpy as jnp
    import jaxlib

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"chip_smoke: {json.dumps(device)} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')} "
          f"compile_cache={cache_dir}", flush=True)

    watch = CompileWatch()

    def native():
        from autodist_tpu.runtime.native import native_available

        if not native_available():
            raise AssertionError(
                "native runtime did not build from native/*.cpp (the "
                "compiler's output is in the WARNING above)")
        return {"built": True}

    run_phase(watch, "native", native)
    run_phase(watch, "kernels", kernels_phase, **FULL_KERNELS,
              interpret=False)
    spec, params = make_lm(FLAGSHIP_LM, jnp.bfloat16)
    run_phase(watch, "train_1chip", train_phase, spec, params,
              strategy="AllReduce", mesh_axes={"data": 1}, batch_size=8,
              steps=5)
    run_phase(watch, "train_mla_moe", train_moe_phase, FULL_MLA_MOE,
              batch_size=2, steps=4)
    run_phase(watch, "train_dsa_moe", train_dsa_moe_phase, FULL_DSA_MOE,
              batch_size=1, steps=2)
    run_phase(watch, "train_swa_moe", train_swa_moe_phase, FULL_SWA_MOE,
              batch_size=1, steps=2, tol=2e-2)
    run_phase(watch, "train_gdn_moe", train_gdn_moe_phase, FULL_GDN_MOE,
              batch_size=2, steps=3, tol=2e-2)
    run_phase(watch, "train_sconv_moe", train_sconv_moe_phase,
              FULL_SCONV_MOE, batch_size=2, steps=3, tol=1e-5)
    run_phase(watch, "serve_paged", serve_paged_phase, spec, params,
              sizes=FULL_SIZES, engine=FULL_ENGINE)
    run_phase(watch, "serve_slots", serve_slots_phase, spec, params,
              sizes=FULL_SIZES,
              engine=dict(slots=8, window=2048, chunk=16))
    run_phase(watch, "serve_exact_f32", serve_exact_phase,
              *make_lm(FLAGSHIP_LM, jnp.float32), sizes=FULL_SIZES,
              engine=FULL_ENGINE)
    if len(devices) >= 4:
        from jax.sharding import NamedSharding, PartitionSpec

        from autodist_tpu.mesh import build_mesh

        run_phase(watch, "train_data4", train_phase, spec, params,
                  strategy="AllReduce", mesh_axes={"data": 4},
                  batch_size=32, steps=5)
        run_phase(watch, "train_data2_model2", train_phase, spec, params,
                  strategy="PartitionedPS",
                  mesh_axes={"data": 2, "model": 2}, batch_size=16,
                  steps=3)
        mesh = build_mesh({"model": 4}, devices=devices[:4])
        run_phase(watch, "serve_paged_model4", serve_paged_phase, spec,
                  jax.device_put(params,
                                 NamedSharding(mesh, PartitionSpec())),
                  sizes=FULL_SIZES, engine=FULL_ENGINE, mesh=mesh)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
