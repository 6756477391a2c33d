"""Entry ``train``: ``AutoDist -> create_distributed_session -> run``.

Set-up builds ONE session, drives it through its first steps from the
seed (which compiles the step, and is what ``correct`` is decided on),
and hands that same session to the measured window.  The steps of the
check and the steps of the window go through the same ``_step`` call:
a fresh host batch from the traffic generator, placed by ``sess.run``,
ended by ``block_until_ready`` on the new parameters.
"""
from __future__ import annotations

import gc
import math
import re
import statistics
import time

import numpy as np

CHECK_STEPS = 3
ADAM_B1 = 0.9          # optax.adamw's default; mu_1 = (1 - b1) * g_1


def _mesh_devices(run, mesh_axes):
    n = math.prod(mesh_axes.values())
    if n != len(run.devices):
        raise SystemExit(f"benchmark: mesh {mesh_axes} needs {n} devices, "
                         f"the cell has {len(run.devices)}")
    return run.devices


def build_spec(config: dict):
    import importlib

    import jax.numpy as jnp

    prog = config["program"]
    mod, fn = prog["factory"].rsplit(".", 1)
    kwargs = dict(prog["kwargs"])
    kwargs["dtype"] = getattr(jnp, kwargs["dtype"])
    return getattr(importlib.import_module(mod), fn)(**kwargs)


def build_session(run, spec, params):
    """The program's normal way in (chip_smoke.py's train_phase)."""
    import optax

    from autodist_tpu import strategy as strategies
    from autodist_tpu.autodist import (AutoDist,
                                       _reset_default_autodist_for_testing)
    from autodist_tpu.mesh import build_mesh

    w = run.cell.workload
    mesh_axes = dict(w["mesh_axes"])
    devices = _mesh_devices(run, mesh_axes)
    _reset_default_autodist_for_testing()     # one AutoDist per process
    ad = AutoDist(strategy_builder=getattr(strategies, w["strategy"])(),
                  mesh_axes=mesh_axes)
    with ad.scope():
        ad.capture(params=params, optimizer=optax.adamw(1e-3),
                   loss_fn=spec.loss_fn, sparse_vars=spec.sparse_vars)
    return ad.create_distributed_session(
        mesh=build_mesh(mesh_axes, devices=devices))


def _step(run, sess, batches):
    """One step as the window takes it.  Returns (loss, seconds)."""
    import jax

    t0 = time.perf_counter()
    with run.spans("bench/make_batch"):
        batch = {"tokens": next(batches)}
    with run.spans("bench/sess.run"):
        out = sess.run(batch)
        jax.block_until_ready(sess.sharded_params)
    return float(out["loss"]), time.perf_counter() - t0


def _mu_leaves(opt_state):
    """The first-moment tree of the session's AdamW state."""
    import jax

    found = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise SystemExit(f"benchmark: expected one Adam state in the "
                         f"session's optimizer state, found {len(found)}")
    return found[0].mu


def worst_leaf(got: dict, want: dict) -> tuple:
    """Largest |got - want| over leaves, against the reference's norm of
    that leaf or of the median leaf, whichever is larger, and that leaf's
    name."""
    if set(got) != set(want):
        raise SystemExit("benchmark: the program's and the reference's "
                         "parameter trees differ in their leaves")
    floor = statistics.median(want.values())
    gap = {k: abs(got[k] - want[k]) / max(want[k], floor) for k in want}
    worst = max(gap, key=gap.get)
    return gap[worst], worst


def worst_leaf_gap(got: dict, want: dict) -> float:
    return worst_leaf(got, want)[0]


def sample_errors(got: dict, want: dict) -> tuple:
    """Element by element over the sampled places of every leaf: the
    pooled relative error sqrt(sum |got - want|^2 / sum |want|^2), and the
    worst leaf's (its error against its own norm or the median leaf's)."""
    num = {k: float(np.sum((got[k] - want[k]) ** 2)) for k in want}
    den = {k: float(np.sum(want[k] ** 2)) for k in want}
    floor = statistics.median(den.values())
    pooled = math.sqrt(sum(num.values()) / sum(den.values()))
    worst = max(math.sqrt(num[k] / max(den[k], floor)) for k in want)
    return pooled, worst


_ELEMENT_BITS = re.compile(r"^(?:bf|f|i|ui)(\d+)")
_PRODUCTS = ("stablehlo.dot_general", "stablehlo.convolution",
             "@tpu_custom_call")


def narrow_product_operands(stablehlo: str, stated: str,
                            exempt=()) -> int:
    """How many operands and results of matrix products and of kernels
    (``dot_general``, ``convolution``, Pallas custom calls) in a lowered
    program are of a narrower type than ``stated`` (``"float32"``,
    ``"bfloat16"``), tensors of the ``exempt`` dims (``"50257x1024"``:
    a table the configuration states to be narrower) left out.  Numbers
    cannot tell float32 activations from bfloat16 ones closely once every
    product rounds its operands to bfloat16 anyway (PERF.md section 6);
    the types can, exactly."""
    want = int(_ELEMENT_BITS.match(
        {"float32": "f32", "bfloat16": "bf16"}[stated]).group(1))
    narrow = 0
    for line in stablehlo.splitlines():
        if not any(op in line for op in _PRODUCTS) or " : (" not in line:
            continue
        for dims in re.findall(r"tensor<([^>]*)>",
                               line.rsplit(" : (", 1)[1]):
            shape, _, element = dims.rpartition("x")
            bits = _ELEMENT_BITS.match(element)
            narrow += (bool(bits) and 1 < int(bits.group(1)) < want
                       and shape not in exempt)
    return narrow


def stated_tables(config: dict) -> tuple:
    """The dims of the embedding table where the configuration states it
    narrower than the rest (the tied head multiplies by it as stored)."""
    p = config["precision"]
    if p.get("tables", p["operands"]) == p["operands"]:
        return ()
    return (f"{config['vocab_size']}x{config['n_embd']}",)


def reference_module(config: dict):
    """The configuration's plain reference: ``benchmark/reference/<name>.py``
    by the name its file gives."""
    import importlib

    return importlib.import_module(
        "benchmark.reference." + config["reference"])


def reference_steps(run, shapes, check_batches, compute=None):
    """The reference's first steps from the same seeded weights, before
    the program's state exists, in the precision the configuration
    states (``precision.reference``) or, for a control, in ``compute``.
    On a cell of several chips the rows of every block are spread over
    them (weights replicated, ``row_block`` rows a chip): the same
    arithmetic, a quarter of the wait."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from benchmark import weights

    gpt2 = reference_module(run.cell.config)
    mesh = Mesh(np.array(run.devices), ("rows",))
    ref = gpt2.to_reference(weights.make_weights(
        shapes, run.seed, NamedSharding(mesh, PartitionSpec())))
    by_rows = NamedSharding(mesh, PartitionSpec("rows"))
    rb = int(run.cell.workload.get("reference_row_block", 1)) \
        * len(run.devices)
    with jax.default_matmul_precision("highest"):
        out = gpt2.train_steps(
            ref, [jax.device_put(jnp.asarray(b), by_rows)
                  for b in check_batches],
            row_block=rb, sample_seed=run.seed,
            compute=compute or run.cell.config["precision"]["reference"])
    del ref
    gc.collect()
    return out


def slowest_steps(run, step_s, keep: int = 3):
    """Print the window's slowest steps beside its median, each with the
    seconds of its two spans (the last ``2 * steps`` the run recorded), so
    that a run that reads low says where its time went; and the steps a
    second of each half of the window, which differ where the rate
    drifts."""
    spans = run.spans.records[-2 * len(step_s):]
    med = statistics.median(step_s)
    worst = sorted(range(len(step_s)), key=step_s.__getitem__)[-keep:]
    rows = [{"step": i, "at_s": round(sum(step_s[:i]), 2),
             "step_ms": round(step_s[i] * 1e3, 2),
             **{n.split("/")[1] + "_ms": round((t1 - t0) * 1e3, 2)
                for n, t0, t1 in spans[2 * i:2 * i + 2]}}
            for i in reversed(worst)]
    over = sum(x - med for x in step_s if x > 1.5 * med)
    run.counters["slow_steps"] = rows
    half = len(step_s) // 2
    halves = [round(len(part) / sum(part), 4)
              for part in (step_s[:half], step_s[half:]) if part]
    print(f"steps: median {med * 1e3:.2f} ms; {over:.3f} s of the window "
          f"in steps over 1.5 x the median; steps/s by half {halves}; "
          f"slowest {rows}", flush=True)


def run(run, reference=None):
    """``reference``: what :func:`reference_steps` gave for this seed,
    where the caller has it already (``control.py`` judges a sound and a
    broken program on one seed against one reading of the reference)."""
    import jax

    from benchmark import traffic, weights

    cell = run.cell
    gpt2 = reference_module(cell.config)
    limits = cell.workload["limits"]
    spec = build_spec(cell.config)
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    batches = traffic.lm_batches(cell.traffic, cell.config["vocab_size"],
                                 run.seed)
    check_batches = [next(batches) for _ in range(CHECK_STEPS)]

    # -- the reference first: its state is gone before the program's is made
    if reference is None:
        with run.outside_setup(), run.spans("bench/reference"):
            t0 = time.perf_counter()
            reference = reference_steps(run, shapes, check_batches)
            run.counters["reference_s"] = time.perf_counter() - t0
        print(f"reference: {CHECK_STEPS} steps in "
              f"{run.counters['reference_s']:.1f} s (not set-up)", flush=True)
    ref_losses, ref_grad, ref_delta, ref_sample = reference

    # -- the program: one session, checked, then measured
    params = weights.make_weights(shapes, run.seed)
    sess = build_session(run, spec, params)
    del params
    feed = iter(check_batches)
    losses, first_grad = [], None
    for i in range(CHECK_STEPS):
        loss, _ = _step(run, sess, feed)
        losses.append(loss)
        if i == 0:
            mu = _mu_leaves(sess.opt_state)
            first_grad = {k: v / (1.0 - ADAM_B1) for k, v in
                          gpt2.flatten(gpt2.leaf_norms(mu)).items()}
            first_sample = {k: v / (1.0 - ADAM_B1) for k, v in
                            gpt2.flatten_samples(gpt2.sample_elements(
                                mu, run.seed)).items()}
            del mu
    from jax.sharding import NamedSharding, PartitionSpec

    with run.outside_setup(), run.spans("bench/lowered_types"):
        narrow = narrow_product_operands(
            sess.lower_step({"tokens": check_batches[0]}).as_text(),
            cell.config["precision"]["operands"], stated_tables(cell.config))
    p0 = weights.make_weights(       # again from the seed, on the mesh
        shapes, run.seed, NamedSharding(sess.mesh, PartitionSpec()))
    delta = gpt2.flatten(gpt2.leaf_diff_norms(sess.export_state()[0], p0))
    del p0
    gc.collect()
    with run.spans("bench/warm"):       # one more step, off the record
        _step(run, sess, batches)

    loss_gaps = [abs(a - b) for a, b in zip(losses, ref_losses)]
    run.counters["check_losses"] = {"program": losses,
                                    "reference": ref_losses,
                                    "gaps": loss_gaps}
    run.check("loss_gap_max", max(loss_gaps), limits["loss_gap_max"])
    grad_gap, grad_leaf = worst_leaf(first_grad, ref_grad)
    run.check("first_grad_norm_gap_worst_leaf", grad_gap,
              limits["first_grad_norm_gap_worst_leaf"])
    delta_gap, delta_leaf = worst_leaf(delta, ref_delta)
    run.check("param_change_norm_gap_worst_leaf", delta_gap,
              limits["param_change_norm_gap_worst_leaf"])
    print(f"worst leaves: first gradient {grad_leaf}, change {delta_leaf}; "
          f"loss gap by step {loss_gaps}", flush=True)
    run.check("product_operands_narrower_than_stated", narrow, 0)
    pooled, worst = sample_errors(first_sample, ref_sample)
    run.check("first_grad_sample_rel_err", pooled,
              limits["first_grad_sample_rel_err"])
    print(f"first_grad_sample_rel_err by the worst leaf (printed, not "
          f"judged: it swings from seed to seed): {worst!r}", flush=True)

    # -- the measured window
    tokens_per_step = check_batches[0].size
    step_s, window_losses = [], []
    start = run.begin_window()
    end = start
    while end - start < run.seconds:
        run.tracer.poll(end - start)
        loss, dt = _step(run, sess, batches)
        step_s.append(dt)
        window_losses.append(loss)
        end = time.perf_counter()
    run.tracer.stop()
    run.end_window()

    steps = len(step_s)
    run.attempted = steps
    run.failed = sum(1 for x in window_losses if not np.isfinite(x))
    run.check("window_losses_not_finite", run.failed, 0)
    run.e2e["train_tokens_s_chip"] = (steps * tokens_per_step
                                      / (end - start) / len(run.devices))
    slowest_steps(run, step_s)
    run.counters.update(
        steps=steps, step_s=step_s, tokens_per_step=tokens_per_step,
        window_s=end - start, seq_len=int(check_batches[0].shape[1]),
        global_batch=int(check_batches[0].shape[0]),
        dispatch_s=[r.phases.get("dispatch") for r in
                    (sess.telemetry.records if sess.telemetry else [])
                    ][-steps:],
        last_loss=window_losses[-1] if window_losses else None)
    return sess
