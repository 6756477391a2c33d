"""Read, on the chip and at a cell's own size, the two numbers every limit
of ``correct`` is set from: what sound runs of the program give, and what
the CONTROL gives.

    python benchmark/control.py --workload <cell> --seeds 11,12,13 \
        [--seconds 12] [--program 0|1]

The controls are the reference computed in the lower precisions that the
configuration's file lists (``precision.controls``: bfloat16 storage, the
nearest below; int8 products, a further rung), put in the program's place
and judged by the same comparison against the reference at the stated
precision (``precision.reference``).  One process reads all the seeds.

* a ``train`` cell: per seed, the reference's first three steps in
  float32 and again under each control, and the gaps between them.  No
  program, no window (the program's own gaps are printed by every run of
  ``run.py``; pass ``--program 1`` to read them here as well).
* a ``serve`` cell: per seed, the server is built with that seed's
  weights and loaded for a short window at the cell's own rate; the
  requests it finished are sampled as ``run.py`` samples them and judged
  as the program (the tokens served) and as each control (the tokens
  the control puts first at the same positions).

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

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def controls(cell) -> dict:
    """``{"control_<name>": compute}`` as the configuration lists them
    (the serving configuration, in no cell yet, lists none)."""
    names = cell.config["precision"].get("controls", ["bfloat16", "int8"])
    return {"control_" + c: c for c in names}


def train_seed(cell, devices, seed: int, with_program: bool) -> dict:
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
    check = [jnp.asarray(next(batches)) for _ in range(train.CHECK_STEPS)]
    rb = int(cell.workload.get("reference_row_block", 1))
    p0 = gpt2.to_reference(weights.make_weights(shapes, seed))
    out = {"seed": seed}
    with jax.default_matmul_precision("highest"):
        ref = gpt2.train_steps(p0, check, row_block=rb, sample_seed=seed,
                               compute=cell.config["precision"]["reference"])
        ctls = {name: gpt2.train_steps(p0, check, row_block=rb,
                                       compute=compute, sample_seed=seed)
                for name, compute in controls(cell).items()}
    for name, ctl in ctls.items():
        pooled, worst = train.sample_errors(ctl[3], ref[3])
        out[name] = {
            "product_operands_narrower_than_stated":
                train.narrow_product_operands(
                    gpt2.lowered_block_grad(p0, check[0][:rb],
                                            controls(cell)[name]),
                    cell.config["precision"]["operands"],
                    train.stated_tables(cell.config)),
            "loss_gap_max": max(abs(a - b) for a, b in zip(ctl[0], ref[0])),
            "first_grad_norm_gap_worst_leaf":
                train.worst_leaf_gap(ctl[1], ref[1]),
            "param_change_norm_gap_worst_leaf":
                train.worst_leaf_gap(ctl[2], ref[2]),
            "first_grad_sample_rel_err": pooled,
            "first_grad_sample_rel_err_worst_leaf": worst}
    del p0
    gc.collect()
    if with_program:
        run = harness.Run(cell, seed, 1.0, False, devices, T0)
        train.run(run)
        out["program"] = {name: value for name, value, _, _ in run.checks}
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--program", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import harness
    from benchmark.run import place_caches

    cell = harness.Cell(args.workload)
    place_caches()
    devices = harness.require_chip(cell)
    rows = []
    for seed in [int(x) for x in args.seeds.split(",")]:
        if cell.workload["entry"] == "train":
            row = train_seed(cell, devices, seed, bool(args.program))
        else:
            row = serve_seed(cell, devices, seed, args.seconds)
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for side in ["program"] + list(controls(cell)):
        have = [r[side] for r in rows if side in r]
        for tag, pick in (("_largest", max), ("_smallest", min)):
            if have:
                summary[side + tag] = {
                    k: pick(h[k] for h in have) for k in have[0]}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
