"""The program's OWN spans, for the per-layer readers under ``metrics/``.

The program (``autodist_tpu/telemetry/timeline.py: host_span``) names its
phases twice from the same clock reads:

* as ``jax.profiler.TraceAnnotation("autodist/<name>", **ids)``: while the
  profiler runs they are events on ``/host:CPU`` of the ``.xplane.pb``, on
  the clock of the device planes.  :func:`trace_spans` reads them and
  :func:`idle_by_span` splits chip 0's idle time among them by EXACT
  overlap: every idle interval is cut at span edges and each piece goes
  to the innermost span over it (``xplane.Reduction.idle_gaps`` gives a
  whole gap to the one span that covers most of it);
* as records ``{name, start, end, parent, ids}`` on ``perf_counter`` in
  the process span ring, profiler on or off.  :func:`ring` reads it: a
  reader gets the ring by calling it, there is nothing to pass.  The
  benchmark's own ``bench/...`` records (``run.spans.records``) are on the
  same clock, so a reader can tell what lay inside one.

The span names are the program's (the constants of ``timeline.py``),
written out here: a reader must also load, and return None, against a
program that has no such spans.
"""
from __future__ import annotations

import heapq
from collections import defaultdict

from benchmark import xplane

PREFIX = "autodist/"
NO_SPAN = "(no span)"


def ring() -> list:
    """The records of the program's span ring that carry both clock
    stamps; ``[]`` where the program keeps none."""
    try:
        from autodist_tpu.telemetry.profiler import get_span_writer

        return [s for s in get_span_writer().spans
                if "start" in s and "end" in s]
    except ImportError:   # a program without the ring
        return []


def ring_spans(name: str, since: float = None, before: float = None) -> list:
    """The ring's records of one name, optionally those that started at
    or after ``since`` or before ``before`` (``perf_counter``)."""
    return [s for s in ring() if s["name"] == name
            and (since is None or s["start"] >= since)
            and (before is None or s["start"] < before)]


def trace_spans(path_or_bytes) -> list:
    """``(name, start_ns, end_ns, ids)`` of every ``autodist/`` event on
    ``/host:CPU``, the prefix taken off the name."""
    from jax.profiler import ProfileData

    data = (ProfileData.from_serialized_xspace(path_or_bytes)
            if isinstance(path_or_bytes, (bytes, bytearray))
            else ProfileData.from_file(path_or_bytes))
    out = []
    for plane in data.planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            out.extend(
                (ev.name[len(PREFIX):], ev.start_ns,
                 ev.start_ns + ev.duration_ns, dict(ev.stats))
                for ev in line.events if ev.name.startswith(PREFIX))
    return out


def innermost_segments(spans) -> list:
    """Disjoint ``(start, end, name)`` covering the union of ``spans``
    (``(name, start, end, ...)``): over each piece, the span that began
    last among those open (the innermost of a thread's nest)."""
    edges = sorted({t for s in spans for t in (s[1], s[2])})
    by_start = sorted(spans, key=lambda s: s[1])
    open_, segments, i = [], [], 0        # heap of (-start, end, name)
    for a, b in zip(edges, edges[1:]):
        while i < len(by_start) and by_start[i][1] <= a:
            s = by_start[i]
            heapq.heappush(open_, (-s[1], s[2], s[0]))
            i += 1
        while open_ and open_[0][1] <= a:
            heapq.heappop(open_)
        if open_:
            segments.append((a, b, open_[0][2]))
    return segments


def idle_by_span(reduction, spans) -> dict:
    """Seconds in which chip 0 ran nothing, by the innermost of ``spans``
    over them (:data:`NO_SPAN` where there is none).  The values add up
    to chip 0's idle time over the reduction's window."""
    busy = reduction._busy[0][0]
    edges = [reduction.t0] + [x for iv in busy for x in iv] + [reduction.t1]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    segments = innermost_segments(spans)
    by, j = defaultdict(float), 0
    for gs, ge in idle:
        while j < len(segments) and segments[j][1] <= gs:
            j += 1
        covered, k = 0.0, j
        while k < len(segments) and segments[k][0] < ge:
            s, e, name = segments[k]
            piece = min(e, ge) - max(s, gs)
            by[name] += piece / 1e9
            covered += piece
            k += 1
        by[NO_SPAN] += (ge - gs - covered) / 1e9
    return dict(by)


def idle_split(run):
    """Chip 0's idle seconds of the traced window by program span, read
    once a run; None without a trace, without a chip in it, or where the
    program wrote no ``autodist/session/`` span into it."""
    memo = "idle_by_program_span"
    if memo not in run.counters:
        red, path = run.trace_reduction, run.tracer.xplane_path()
        spans = trace_spans(path) if red is not None and red.chips \
            and path is not None else []
        run.counters[memo] = idle_by_span(red, spans) if any(
            s[0].startswith("session/") for s in spans) else None
    return run.counters[memo]


def idle_pct(run, names) -> float:
    """Share (%) of the traced window in which chip 0 was idle under one
    of the spans ``names``; None where :func:`idle_split` is."""
    by = idle_split(run)
    if by is None or not run.trace_reduction.window_s:
        return None
    return 100.0 * sum(by.get(n, 0.0) for n in names) \
        / run.trace_reduction.window_s


def union_seconds(spans, leave_out=()) -> float:
    """Length of the union of the records' ``[start, end]``, those that
    lie wholly inside one of the ``leave_out`` intervals left out."""
    kept = [(s["start"], s["end"]) for s in spans
            if not any(a <= s["start"] and s["end"] <= b
                       for a, b in leave_out)]
    return xplane.union(kept)[1]
