"""``cut_trace.py`` for a trace whose readers want the operations' SCOPES:
keeps, for chip 0, whole step programs and their longer operations as
``cut_trace.py`` does, and with every operation the ``tf_op`` string of
its metadata (the ``op_name`` XLA kept: ``jit(step)/.../moe/route/...``),
which ``jax.profiler.ProfileData`` does not show and
``metrics/moe_routed_device_pct.py`` reads from the file itself.

    python benchmark/tests/cut_trace_scopes.py <in.xplane.pb> <out.xplane.pb> \
        [--runs 1] [--min-us 100] [--keep REGEX]

Names, starts, durations and scopes are the recorded ones.
"""
from __future__ import annotations

import argparse
import importlib.util
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_reader():
    spec = importlib.util.spec_from_file_location(
        "moe_routed_device_pct", os.path.join(
            os.path.dirname(HERE), "metrics", "moe_routed_device_pct.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def quoted(text: str) -> str:
    return text.replace("\\", "\\\\").replace('"', '\\"')


def main(argv=None) -> int:
    from jax.profiler import ProfileData

    ap = argparse.ArgumentParser()
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--runs", type=int, default=1)
    ap.add_argument("--min-us", type=float, default=100.0)
    ap.add_argument("--keep", default=r"custom_call_target")
    args = ap.parse_args(argv)
    keep = re.compile(args.keep)
    reader = load_reader()
    strings = reader.operation_strings(args.src)
    plane = next(p for p in ProfileData.from_file(args.src).planes
                 if p.name == reader.DEVICE_PLANE)
    lines = {ln.name: list(ln.events) for ln in plane.lines}
    mods = sorted(lines["XLA Modules"],
                  key=lambda e: e.start_ns)[1:1 + args.runs]
    lo, hi = mods[0].start_ns, mods[-1].start_ns + mods[-1].duration_ns
    ops = [e for e in lines["XLA Ops"]
           if e.start_ns >= lo and e.start_ns + e.duration_ns <= hi
           and (e.duration_ns >= args.min_us * 1e3 or keep.search(e.name))]
    meta, body = {}, []
    for lid, (lname, events) in enumerate(
            (("XLA Modules", mods), ("XLA Ops", ops)), 1):
        body.append(f'  lines {{ id: {lid} name: "{lname}" timestamp_ns: 0')
        for e in events:
            mid = meta.setdefault(e.name, len(meta) + 1)
            body.append(
                f"    events {{ metadata_id: {mid} offset_ps: "
                f"{int(round((e.start_ns - lo) * 1000))} duration_ps: "
                f"{int(round(e.duration_ns * 1000))} }}")
        body.append("  }")
    out = [f'planes {{ id: 1 name: "{reader.DEVICE_PLANE}"'] + body
    for name, mid in meta.items():
        scope = strings.get(name, {}).get("tf_op", "")
        stat = (f' stats {{ metadata_id: 1 str_value: "{quoted(scope)}" }}'
                if scope else "")
        out.append(f'  event_metadata {{ key: {mid} value {{ id: {mid} '
                   f'name: "{quoted(name)}"{stat} }} }}')
    out += ['  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }', "}"]
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(out))
    with open(args.dst, "wb") as f:
        f.write(blob)
    print(f"{args.dst}: {len(blob)} bytes, {len(mods) + len(ops)} events")
    return 0


if __name__ == "__main__":
    sys.exit(main())
