"""Read, on the chip and at a training cell's own size, that the cell's
limits tell the stated model from ANOTHER MODEL: the program as it ships,
through the entry's own checks (``control.py: train_program``), judged
against the cell's reference computing a model with one part left out.
Every such run has to come out ``correct: false``.

    python benchmark/wrong_models.py --workload <cell> --seeds 11,12 \
        --wrong no_conv,no_b_gate,no_qk_norm,no_rotary [--root <dir>]

The names are the reference's own (its ``Settings.wrong``: ``reference/
lfm2_moe.py`` says what each leaves out); a reference without such a
field has no other model to offer.  Prints one JSON line a seed and name
with every number the run compared and the limits it failed, and exits 1
where one was judged correct.  The benchmark's own runs never call this; ``tests/
test_lfm2_moe.py`` keeps the same comparison at a size a test can hold.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import control   # noqa: E402


def another_model(bound, wrong: str):
    """The reference module ``bound`` to a configuration, computing the
    model ``wrong`` names in the stated one's place."""
    s = dataclasses.replace(bound.SETTINGS, wrong=wrong)
    return types.SimpleNamespace(**{
        **vars(bound), "SETTINGS": s, **{
            name: functools.partial(getattr(bound, name).func, s=s)
            for name in ("train_steps", "lowered_block_grad")}})


def judged(cell, devices, seed: int, wrong: str) -> dict:
    """The program's checks against the other model's first steps."""
    import jax

    from benchmark import harness, traffic
    from benchmark.entries import train

    stated = train.reference_module
    other = another_model(stated(cell.config), wrong)
    spec = train.build_spec(cell.config)
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    batches = traffic.lm_batches(cell.traffic, cell.config["vocab_size"],
                                 seed)
    check = [next(batches) for _ in range(train.CHECK_STEPS)]
    train.reference_module = lambda config: other
    try:
        reference = train.reference_steps(
            harness.Run(cell, seed, 1.0, False, devices, control.T0),
            shapes, check)
    finally:
        train.reference_module = stated
    row = control.train_program(cell, devices, seed, reference)
    limits = dict(cell.workload["limits"],
                  product_operands_narrower_than_stated=0)
    return dict(row, seed=seed, wrong=wrong,
                failed=[k for k, limit in limits.items() if row[k] > limit])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--wrong", required=True, type=control._names)
    ap.add_argument("--root", default=None)
    args = ap.parse_args(argv)

    from benchmark import harness
    from benchmark.run import place_caches

    cell = harness.Cell(args.workload, root=os.path.abspath(
        args.root or harness.ROOT))
    place_caches()
    devices = harness.require_chip(cell)
    passed = 0
    for seed in [int(x) for x in args.seeds.split(",")]:
        for wrong in args.wrong:
            row = judged(cell, devices, seed, wrong)
            passed += row["correct"]
            print(json.dumps(row), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
