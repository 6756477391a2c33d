"""Share of a step's device time in the routed experts: route (scores,
top-k, sort), the gather into expert order, the grouped products, and
the combine back to token order; the shared experts (dense) are not in it.

The program names those parts ``jax.named_scope("moe/route")``,
``"moe/experts"`` and ``"moe/combine"``.  XLA keeps the scope in every
operation's ``op_name``, and the profiler writes that as the stat
``tf_op`` of the operation's METADATA in the ``.xplane.pb`` (the event's
name is the HLO text and carries no scope; ``jax.profiler.ProfileData``
shows an event's own stats only).  So this reader goes back to the file
for the metadata: a protobuf is read with nothing but its wire format
(``xplane.proto`` of the profiler: the field numbers below), the
operations are matched to the trace's events by name, and the events are
summed as ``xplane.Reduction`` sums them.  The grouped products' own
kernels are found by their HLO name instead (``ragged-dot-none``,
``ragged-dot-metadata``): XLA makes those calls from ``jax.lax.
ragged_dot`` after the scopes are gone (their ``tf_op`` is
``ragged-dot-none:``).  A fusion formed across two scopes counts where
XLA's metadata puts it.  None without a trace, or where no operation of a
step names such a scope (a program without the layer)."""
import re

from benchmark import xplane

STEP = r"^jit_step\b"
ROUTED = re.compile(r"\bmoe/(route|experts|combine)\b")
GROUPED = re.compile(r"^%?ragged-dot")      # counted as moe/experts
DEVICE_PLANE = "/device:TPU:0"

# xplane.proto: XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
# .stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata.name = 2,
# .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
# .str_value = 5, .ref_value = 7 (the id of a stat_metadata whose NAME is
# the string)
_LEN = 2


def _varint(buf, i):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, wire type, value)`` of one message: an int for a
    varint, a memoryview for a length-delimited or fixed field."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == _LEN:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane.pb")
        yield key >> 3, wire, value


def _only(buf, number):
    return [v for f, w, v in _fields(buf) if f == number and w == _LEN]


def operation_strings(path, plane_name=DEVICE_PLANE) -> dict:
    """``{operation's name: {stat's name: string}}``: every string-valued
    stat of the operations' metadata in one plane of an ``.xplane.pb``
    (``tf_op`` among them)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for plane in _only(space, 1):
        if [bytes(v).decode() for v in _only(plane, 2)] != [plane_name]:
            continue
        stat_names, metadata = {}, []
        for f, w, entry in _fields(plane):
            if w != _LEN or f not in (4, 5):
                continue
            key = next(v for g, _, v in _fields(entry) if g == 1)
            value = _only(entry, 2)[0]
            if f == 5:
                stat_names[key] = bytes(_only(value, 2)[0]).decode() \
                    if _only(value, 2) else ""
            else:
                metadata.append(value)
        for value in metadata:
            name, strings = "", {}
            for f, w, v in _fields(value):
                if f == 2 and w == _LEN:
                    name = bytes(v).decode(errors="replace")
                elif f == 5 and w == _LEN:
                    stat = dict((g, s) for g, _, s in _fields(v))
                    if 5 in stat:
                        text = bytes(stat[5]).decode(errors="replace")
                    elif 7 in stat:
                        text = stat_names.get(stat[7], "")
                    else:
                        continue
                    strings[stat_names.get(stat.get(1), "")] = text
            out[name] = strings
    return out


def scoped_seconds(red, strings: dict, scope=ROUTED, step=STEP,
                   kernels=GROUPED) -> tuple:
    """(seconds of chip 0's operations inside whole step programs, the
    seconds of those whose ``tf_op`` names ``scope``, by what it matched;
    an operation whose NAME matches ``kernels`` goes to ``moe/experts``).
    A loop's own event spans its body's and is left out."""
    chip = red.chips[0]
    runs = [(s, e) for n, s, e in chip.modules if re.search(step, n)]
    where = {}
    total, by = 0.0, {}
    for name, start, end in chip.ops:
        if xplane.CONTAINER.match(name) or not any(
                a <= start and end <= b for a, b in runs):
            continue
        total += (end - start) / 1e9
        if name not in where:
            m = scope.search(strings.get(name, {}).get("tf_op", ""))
            where[name] = m.group(0) if m else (
                "moe/experts" if kernels.match(name) else None)
        if where[name]:
            by[where[name]] = by.get(where[name], 0.0) + (end - start) / 1e9
    return total, by


def read(run):
    red = run.trace_reduction
    path = run.tracer.xplane_path() if red is not None else None
    if path is None or not red.chips:
        return None
    total, by = scoped_seconds(red, operation_strings(path))
    if not total or not by:
        return None
    print("moe routed device time by scope (s of the traced steps): "
          + ", ".join(f"{k} {v:.4f}" for k, v in sorted(by.items()))
          + f"; all operations {total:.4f}", flush=True)
    return 100.0 * sum(by.values()) / total
