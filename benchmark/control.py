"""Read, on the chip and at a cell's own size, the two numbers every limit
of ``correct`` is set from: what sound runs of the program give, and what
the CONTROL gives.

    python benchmark/control.py --workload <cell> --seeds 11,12,13 \
        [--seconds 12] [--program 0|1] [--controls bfloat16,int8] \
        [--broken rows_left_out,frozen] [--root <dir>]

The controls are the reference computed in the lower precisions that the
configuration's file lists (``precision.controls``: bfloat16 storage, the
nearest below; int8 products, a further rung), put in the program's place
and judged by the same comparison against the reference at the stated
precision (``precision.reference``).  One process reads all the seeds.

* a ``train`` cell: per seed, the reference's first three steps at the
  stated precision and again under each control, and the gaps between
  them.  On a cell of several chips the rows of every block are spread
  over them as in a run (``entries/train.py: reference_steps``).  No
  window.  ``--program 1`` reads the program's own gaps against the same
  reading of the reference (every run of ``run.py`` prints them too), with
  each step's loss gap beside the largest.  ``--broken`` reads them again
  with the timed path broken underneath, at the cell's own size
  (:data:`BROKEN`): the upper readings of the limits that precision
  hardly moves.
* a ``serve`` cell: per seed, the server is built with that seed's
  weights and loaded for a short window at the cell's own rate; the
  requests it finished are sampled as ``run.py`` samples them and judged
  as the program (the tokens served) and as each control (the tokens
  the control puts first at the same positions).

``--root`` takes the cell from a directory with a ``BENCHMARK.json`` of
its own (as ``tests/data``): a scratch cell that separates two causes.

Prints one JSON line per seed and a last line with the largest and the
smallest of every number on each side.  The benchmark's own runs
never call this; ``benchmark/tests/test_correct.py`` keeps the same
comparison at a size a test run can hold.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse   # noqa: E402
import gc         # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Frozen:
    """A session whose step computes its loss and returns its state
    unchanged."""

    def __init__(self, sess):
        self._sess = sess

    def run(self, batch):
        return self._sess.evaluate(batch)

    def __getattr__(self, name):
        return getattr(self._sess, name)


class RowsLeftOut(Frozen):
    """A session whose step leaves out one chip's rows of the batch (on
    one chip: the second half), the first rows taking their place."""

    def run(self, batch):
        tokens = batch["tokens"]
        out = tokens.shape[0] // max(2, self._sess.mesh.size)
        return self._sess.run({"tokens": np.concatenate(
            [tokens[:-out], tokens[:out]])})


#: the timed path broken underneath (``--broken``, and the tests)
BROKEN = {"frozen": Frozen, "rows_left_out": RowsLeftOut}


def controls(cell, only=None) -> dict:
    """``{"control_<name>": compute}`` as the configuration lists them
    (the serving configuration, in no cell yet, lists none)."""
    names = cell.config["precision"].get("controls", ["bfloat16", "int8"])
    return {"control_" + c: c for c in names if not only or c in only}


def train_program(cell, devices, seed: int, reference, broken=None) -> dict:
    """One session of the program through the entry's own checks, judged
    against ``reference``; ``broken`` wraps the session the entry builds."""
    from autodist_tpu.autodist import _reset_default_autodist_for_testing
    from benchmark import harness
    from benchmark.entries import train

    run = harness.Run(cell, seed, 1.0, False, devices, T0)
    build = train.build_session
    if broken is not None:
        train.build_session = lambda *a, **k: broken(build(*a, **k))
    try:
        train.run(run, reference)
    finally:
        train.build_session = build
    # the process's one AutoDist keeps its session, and that its state
    _reset_default_autodist_for_testing()
    gc.collect()
    return dict({name: value for name, value, _, _ in run.checks},
                correct=run.correct,
                loss_gap_by_step=run.counters["check_losses"]["gaps"])


def train_seed(cell, devices, seed: int, args) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmark import harness, traffic, weights
    from benchmark.entries import train

    gc.collect()
    gpt2 = train.reference_module(cell.config)
    spec = train.build_spec(cell.config)
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    batches = traffic.lm_batches(cell.traffic, cell.config["vocab_size"],
                                 seed)
    check = [next(batches) for _ in range(train.CHECK_STEPS)]
    rb = int(cell.workload.get("reference_row_block", 1))
    run = harness.Run(cell, seed, 1.0, False, devices, T0)
    out = {"seed": seed}
    ref = train.reference_steps(run, shapes, check)
    p0 = gpt2.to_reference(weights.make_weights(shapes, seed))
    for name, compute in controls(cell, args.controls).items():
        ctl = train.reference_steps(run, shapes, check, compute=compute)
        pooled, worst = train.sample_errors(ctl[3], ref[3])
        gaps = [abs(a - b) for a, b in zip(ctl[0], ref[0])]
        out[name] = {
            "product_operands_narrower_than_stated":
                train.narrow_product_operands(
                    gpt2.lowered_block_grad(
                        p0, jnp.asarray(check[0][:rb]), compute),
                    cell.config["precision"]["operands"],
                    train.stated_tables(cell.config)),
            "loss_gap_max": max(gaps),
            "loss_gap_by_step": gaps,
            "first_grad_norm_gap_worst_leaf":
                train.worst_leaf_gap(ctl[1], ref[1]),
            "param_change_norm_gap_worst_leaf":
                train.worst_leaf_gap(ctl[2], ref[2]),
            "first_grad_sample_rel_err": pooled,
            "first_grad_sample_rel_err_worst_leaf": worst}
    del p0
    if args.program:
        out["program"] = train_program(cell, devices, seed, ref)
    for name in args.broken:
        out["program_" + name] = train_program(cell, devices, seed, ref,
                                               BROKEN[name])
    return out


def serve_seed(cell, devices, seed: int, seconds: float) -> dict:
    from benchmark import harness, traffic
    from benchmark.entries import serve

    run = harness.Run(cell, seed, seconds, False, devices, T0)
    reqs = traffic.requests(cell.traffic, int(cell.config["vocab_size"]),
                            seconds, seed)
    rig = serve.Rig(run)
    try:
        child, out_path = rig.spawn(reqs, seconds, 60.0, "control")
        _, recs = rig.load(child, out_path, seconds, 60.0)
        rig.wait_idle(120.0)
    finally:
        rig.close()
    params, rig.params = rig.params, None
    del rig
    gc.collect()
    s = serve.summarize(recs, seconds)
    samples = serve.sample_finished(
        recs, reqs, seed, int(cell.workload.get("check_requests", 4)))
    window = int(cell.workload["engine"]["window"])
    out = {"seed": seed, "sent": s["sent"], "ok": s["ok"],
           "failed": s["failed"],
           "program": serve.served_gaps(params, samples, window)}
    for name, compute in controls(cell).items():
        out[name] = serve.served_gaps(params, samples, window,
                                      control=compute)
    del params
    gc.collect()
    return out


def _names(text: str) -> list:
    return [x for x in text.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--program", type=int, choices=(0, 1), default=0)
    ap.add_argument("--controls", default="", type=_names)
    ap.add_argument("--broken", default="", type=_names)
    ap.add_argument("--root", default=None)
    args = ap.parse_args(argv)

    from benchmark import harness
    from benchmark.run import place_caches

    cell = harness.Cell(args.workload, root=os.path.abspath(
        args.root or harness.ROOT))
    place_caches()
    devices = harness.require_chip(cell)
    rows = []
    for seed in [int(x) for x in args.seeds.split(",")]:
        if cell.workload["entry"] == "train":
            row = train_seed(cell, devices, seed, args)
        else:
            row = serve_seed(cell, devices, seed, args.seconds)
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for side in dict.fromkeys(k for r in rows for k in r if k != "seed"):
        have = [r[side] for r in rows if side in r]
        for tag, pick in (("_largest", max), ("_smallest", min)):
            summary[side + tag] = {
                k: pick(h[k] for h in have) for k in have[0]
                if not isinstance(have[0][k], (list, bool))}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
