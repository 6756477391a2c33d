"""Find the knee of a serving cell once, on the chip.

    python benchmark/sweep.py --workload <serve cell> --seed <n> \
        --seconds <window> --start <rate> --steps <k> [--factor 1.15]

One process, one set-up: the cell's server is built and warmed as
``run.py`` does it, then loaded at rates rising by ``factor`` a step, each
for one window of the cell's own mix (``traffic.requests(..., rate=)``),
with the engine reset in between.  A rate is SUSTAINED when every request
sent completes and the number still in flight at the window's end is no
larger than at its middle.  The knee is the highest sustained rate; the
cells' rates (0.8 x and 1.5 x) are written into their traffic files by
hand, with this table in PERF.md.  Prints one JSON line per rate.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--start", type=float, required=True)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--factor", type=float, default=1.15)
    args = ap.parse_args(argv)

    from benchmark import harness, traffic
    from benchmark.entries import serve
    from benchmark.run import place_caches

    cell = harness.Cell(args.workload)
    place_caches()
    devices = harness.require_chip(cell)
    run = harness.Run(cell, args.seed, args.seconds, False, devices, T0)
    rig = serve.Rig(run)
    vocab = int(cell.config["vocab_size"])
    rate, knee = args.start, None
    try:
        for step in range(args.steps):
            reqs = traffic.requests(cell.traffic, vocab, args.seconds,
                                    args.seed + step, rate=rate)
            child, out = rig.spawn(reqs, args.seconds, 60.0, f"sweep{step}")
            stats0 = rig.srv.stats()
            _, recs = rig.load(child, out, args.seconds, 60.0)
            rig.wait_idle(120.0)
            stats1 = rig.srv.stats()
            s = serve.summarize(recs, args.seconds)
            sustained = (s["failed"] == 0 and s["unfinished"] == 0
                         and s["backlog_end"] <= s["backlog_mid"])
            if sustained:
                knee = rate
            ticks = stats1["ticks"] - stats0["ticks"]
            print(json.dumps({
                "rate_per_s": round(rate, 4), "sent": s["sent"],
                "ok": s["ok"], "failed": s["failed"],
                "unfinished": s["unfinished"],
                "backlog_mid": s["backlog_mid"],
                "backlog_end": s["backlog_end"], "sustained": sustained,
                "out_tokens_s": s["out_tokens_in_window"] / args.seconds,
                "ttft_ms_p50": harness.percentile(s["ttft_ms"], 50),
                "ttft_ms_p95": harness.percentile(s["ttft_ms"], 95),
                "tpot_ms_p50": harness.percentile(s["tpot_ms"], 50),
                "tpot_ms_p95": harness.percentile(s["tpot_ms"], 95),
                "late_ms_p95": harness.percentile(s["late_ms"], 95),
                "occupancy_pct": 100.0 * (
                    stats1["generated_tokens"] - stats0["generated_tokens"])
                / max(ticks * rig.engine_kwargs["slots"], 1),
                "deferred_blocks": stats1["deferred_blocks"]
                - stats0["deferred_blocks"]}), flush=True)
            rig.srv.engine.reset()
            rate *= args.factor
    finally:
        rig.close()
    print(json.dumps({"knee_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
