"""Cut a recorded ``.xplane.pb`` down to a file small enough to keep.

    python benchmark/tests/cut_trace.py <in.xplane.pb> <out.xplane.pb> \
        [--runs 2] [--min-us 30] [--keep REGEX] [--skip 1]

Keeps, for every TPU plane, the first ``--runs`` whole program runs of the
``XLA Modules`` line after the first ``--skip`` (the trace's edge may have
cut the first; 0 for a file that is a cut already) and the ops of
``XLA Ops`` inside them that last at
least ``--min-us`` or whose name matches ``--keep`` (kernels,
collectives), and the host's ``bench/...`` spans that overlap them.
Names, starts and durations are the recorded ones; nothing is invented.
The files under ``benchmark/tests/traces/`` were made with it from traces
of the benchmark's own cells on the v5e (PERF.md says which runs).
"""
from __future__ import annotations

import argparse
import re
import sys


def main(argv=None) -> int:
    from jax.profiler import ProfileData

    ap = argparse.ArgumentParser()
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--runs", type=int, default=2)
    ap.add_argument("--min-us", type=float, default=30.0)
    ap.add_argument("--skip", type=int, default=1)
    ap.add_argument("--keep", default=r"tpu_custom_call|^%?(?:all-reduce|"
                    r"reduce-scatter|all-gather|collective-permute)")
    args = ap.parse_args(argv)
    keep = re.compile(args.keep)
    data = ProfileData.from_file(args.src)
    planes, t_lo, t_hi = [], None, None
    for plane in data.planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        mods = sorted(lines.get("XLA Modules", []),
                      key=lambda e: e.start_ns)[args.skip:args.skip + args.runs]
        if not mods:
            continue
        lo, hi = mods[0].start_ns, mods[-1].start_ns + mods[-1].duration_ns
        t_lo = lo if t_lo is None else min(t_lo, lo)
        t_hi = hi if t_hi is None else max(t_hi, hi)
        ops = [e for e in lines.get("XLA Ops", [])
               if e.start_ns >= lo and e.start_ns + e.duration_ns <= hi
               and (e.duration_ns >= args.min_us * 1e3
                    or keep.search(e.name))]
        planes.append((plane.name, {"XLA Modules": mods, "XLA Ops": ops}))
    spans = []
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for ln in plane.lines:
                spans += [e for e in ln.events
                          if e.name.startswith("bench/")
                          and e.start_ns < t_hi
                          and e.start_ns + e.duration_ns > t_lo]
    planes.append(("/host:CPU", {"python": spans}))

    out, pid = [], 0
    for name, lines in planes:
        pid += 1
        meta, body, lid = {}, [], 0
        for lname, events in lines.items():
            lid += 1
            body.append(f'  lines {{ id: {lid} name: "{lname}" '
                        f'timestamp_ns: 0')
            for e in events:
                mid = meta.setdefault(e.name, len(meta) + 1)
                body.append(
                    f"    events {{ metadata_id: {mid} offset_ps: "
                    f"{int(round((e.start_ns - t_lo) * 1000))} duration_ps:"
                    f" {int(round(e.duration_ns * 1000))} }}")
            body.append("  }")
        out.append(f'planes {{ id: {pid} name: "{name}"')
        out += body
        for ename, mid in meta.items():
            esc = ename.replace("\\", "\\\\").replace('"', '\\"')
            out.append(f'  event_metadata {{ key: {mid} value {{ id: {mid} '
                       f'name: "{esc}" }} }}')
        out.append("}")
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(out))
    with open(args.dst, "wb") as f:
        f.write(blob)
    print(f"{args.dst}: {len(blob)} bytes, "
          f"{sum(len(ev) for _, ls in planes for ev in ls.values())} events")
    return 0


if __name__ == "__main__":
    sys.exit(main())
