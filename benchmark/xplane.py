"""From a profiler trace (``.xplane.pb``) to numbers.  Read with nothing
but JAX (``jax.profiler.ProfileData``).

A TPU trace has one plane per chip (``/device:TPU:<n>``).  Its line
``XLA Ops`` holds one event per executed HLO operation (start, duration);
``XLA Modules`` one per executed program.  The host plane (``/host:CPU``)
holds the benchmark's own ``bench/...`` spans (``TraceAnnotation``), on
the same clock.

* busy: the union of the op intervals of a chip; idle share is 1 - busy /
  window, the window being the traced interval (first to last event over
  all chips, host spans included).
* a kernel's or a program's time: the sum of the durations of the events
  whose name matches.
* exposed collective time: the part of the collective ops' intervals
  during which no other op runs on that chip.
* idle gaps: the longest intervals in which a chip ran nothing, each named
  by the ``bench/...`` span that covers most of it.
"""
from __future__ import annotations

import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench/"
#: control-flow ops whose event spans the events of their own bodies
CONTAINER = re.compile(r"^%?(while|conditional|call)[\w.\-]* = ")
#: by the operation's own name, at the start: the text of an event also
#: names its operands, and an operation that READS ``%all-reduce.7`` is none
COLLECTIVE = re.compile(
    r"^%?(?:all-reduce|reduce-scatter|all-gather|all-to-all|"
    r"collective-permute)", re.I)


def stable_name(name: str) -> str:
    """``%fusion.123 = ...`` / ``fusion.123`` -> ``fusion``: the HLO name
    without its numbering, so that the table survives a recompile."""
    name = name.split(" = ", 1)[0].lstrip("%")
    name = re.sub(r"[.\-_]\d+(?=$|[.\-_])", "", name)
    return re.sub(r"\.\d+$", "", name) or name


def op_label(name: str) -> str:
    """A stable label for the breakdown: the HLO name without its number
    and the type and shape of its (first) result, as in
    ``fusion bf16[50257,1024]``: shapes survive a recompile, numbers do
    not, and ``fusion`` alone says nothing."""
    m = re.match(r"%?[\w.\-]+ = \(?(\w+\[[\d,]*\])", name)
    return stable_name(name) + (" " + m.group(1) if m else "")


def union(intervals):
    """Merged, sorted intervals and their total length."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged, sum(e - s for s, e in merged)


def subtract(a, b):
    """Total length of merged intervals ``a`` not covered by merged ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


class Chip:
    def __init__(self, index: int):
        self.index = index
        self.ops = []        # (name, start_ns, end_ns)
        self.modules = []    # (name, start_ns, end_ns)


class Reduction:
    def __init__(self, chips, spans, n_chips_used: int):
        self.chips = chips[:n_chips_used] if n_chips_used else chips
        self.spans = spans            # (name, start_ns, end_ns) host
        starts = [s for c in self.chips for _, s, _ in c.ops]
        ends = [e for c in self.chips for _, _, e in c.ops]
        starts += [s for _, s, _ in spans]
        ends += [e for _, _, e in spans]
        self.t0 = min(starts) if starts else 0.0
        self.t1 = max(ends) if ends else 0.0
        self.window_s = (self.t1 - self.t0) / 1e9
        self._busy = [union([(s, e) for _, s, e in c.ops])
                      for c in self.chips]
        self.busy_s = (sum(b[1] for b in self._busy) / 1e9
                       / max(len(self.chips), 1))

    # -- shares ------------------------------------------------------------
    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0

    def op_seconds(self, pattern) -> float:
        """Device seconds in ops whose name matches, averaged over chips."""
        rx = re.compile(pattern) if isinstance(pattern, str) else pattern
        tot = sum(e - s for c in self.chips for n, s, e in c.ops
                  if rx.search(n))
        return tot / 1e9 / max(len(self.chips), 1)

    def module_runs(self, pattern):
        """(count, total seconds) of program executions whose name
        matches, on the first chip."""
        rx = re.compile(pattern) if isinstance(pattern, str) else pattern
        runs = [(e - s) / 1e9 for n, s, e in self.chips[0].modules
                if rx.search(n)] if self.chips else []
        return len(runs), sum(runs)

    def ops_in_module_runs(self, module_pattern, op_pattern):
        """(module runs, matching ops, their seconds) on the first chip,
        counting only ops that lie inside a run of a matching program:
        a run cut by the trace's edge brings neither itself nor its ops."""
        mrx, orx = re.compile(module_pattern), re.compile(op_pattern)
        if not self.chips:
            return 0, 0, 0.0
        chip = self.chips[0]
        runs = sorted((s, e) for n, s, e in chip.modules if mrx.search(n))
        ops = sorted((s, e) for n, s, e in chip.ops if orx.search(n))
        count, seconds, j = 0, 0.0, 0
        for rs, re_ in runs:
            while j < len(ops) and ops[j][0] < rs:
                j += 1
            while j < len(ops) and ops[j][1] <= re_:
                count += 1
                seconds += (ops[j][1] - ops[j][0]) / 1e9
                j += 1
        return len(runs), count, seconds

    def exposed_seconds(self, pattern=COLLECTIVE) -> float:
        """Seconds of matching ops during which nothing else runs on the
        same chip, averaged over chips."""
        rx = re.compile(pattern) if isinstance(pattern, str) else pattern
        tot = 0.0
        for c in self.chips:
            coll, _ = union([(s, e) for n, s, e in c.ops if rx.search(n)])
            rest, _ = union([(s, e) for n, s, e in c.ops
                             if not rx.search(n)])
            tot += subtract(coll, rest)
        return tot / 1e9 / max(len(self.chips), 1)

    # -- the breakdown -------------------------------------------------------
    def top_ops(self, k: int = 10):
        by = defaultdict(float)
        for c in self.chips:
            for n, s, e in c.ops:
                if not CONTAINER.match(n):     # its body's ops are listed
                    by[op_label(n)] += (e - s) / 1e9
        n = max(len(self.chips), 1)
        return [[name, sec / n] for name, sec in
                sorted(by.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10):
        """The idle time of chip 0 by what the host was doing: every gap
        between busy intervals goes to the ``bench/...`` span that covers
        most of it (``(no span)`` otherwise); the k largest totals."""
        if not self.chips:
            return []
        merged = self._busy[0][0]
        edges = [self.t0] + [x for iv in merged for x in iv] + [self.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        by = defaultdict(float)
        spans = sorted(self.spans, key=lambda x: x[1])
        for gs, ge in gaps:
            best, cover = "(no span)", 0.0
            for n, s, e in spans:
                if s >= ge:
                    break
                ov = min(e, ge) - max(s, gs)
                # the innermost (shortest) span that covers the gap best
                if ov > cover or (ov == cover and ov > 0
                                  and n.count("/") > best.count("/")):
                    best, cover = n, ov
            by[best] += (ge - gs) / 1e9
        return [[n, s] for n, s in
                sorted(by.items(), key=lambda kv: -kv[1])[:k]]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def reduce(path_or_bytes, n_chips_used: int = 0) -> Reduction:
    from jax.profiler import ProfileData

    data = (ProfileData.from_serialized_xspace(path_or_bytes)
            if isinstance(path_or_bytes, (bytes, bytearray))
            else ProfileData.from_file(path_or_bytes))
    chips, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = chips.setdefault(int(m.group(1)), Chip(int(m.group(1))))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    chip.ops.extend(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events)
                elif line.name == MODULES_LINE:
                    chip.modules.extend(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events
                    if ev.name.startswith(SPAN_PREFIX))
    ordered = [chips[k] for k in sorted(chips)]
    return Reduction(ordered, spans, n_chips_used)


def describe(path: str, limit: int = 12) -> str:
    """What is in a trace, for a human: planes, lines, counts and the
    first event names.  ``python -m benchmark.xplane <file>``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            for ev in evs[:limit]:
                stats = {k: v for k, v in list(ev.stats)[:6]}
                out.append(f"    {ev.name[:100]!r} start={ev.start_ns} "
                           f"dur={ev.duration_ns} {stats}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(describe(sys.argv[1]))
