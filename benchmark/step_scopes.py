"""The whole train step on the device, split by the program's scopes.

The program enters ``jax.named_scope(timeline.SCOPE_*)`` around every part
of a step (``autodist_tpu/telemetry/timeline.py`` holds the ONE list of
names; ``docs/observability.md`` says where each is entered).  XLA keeps
the scopes in an operation's ``op_name`` and the profiler writes that as
the stat ``tf_op`` of the operation's metadata, which
``metrics/moe_routed_device_pct.py: operation_strings`` reads from the
``.xplane.pb`` itself.  This module takes chip 0's operations inside whole
``jit_step`` programs, EACH ONCE: a ``while``, ``conditional``, ``call`` or
``cond`` event spans its body's operations and is left out
(``xplane.CONTAINER`` does not know the ``%cond.N`` that ``lax.switch``
lowers to, so readers on it count a branch twice).  An operation goes to

* ``step/collective`` if its own name is a collective's (GSPMD gives an
  all-reduce the ``op_name`` of the product whose result it sums); else
* the INNERMOST (last) name of the vocabulary in its ``tf_op``:
  ``jit(step)/jvp(lm/layers)/while/body/closed_call/moe/route/top_k`` is
  ``moe/route``, and what the layers' map, checkpoints and autodiff add
  between the named parts is ``lm/layers`` and nothing deeper; else
* ``moe/experts`` if it is a ``ragged-dot-*`` kernel (XLA makes those
  calls after the scopes are gone), as ``moe_routed_device_pct`` does; else
* ``unscoped``.

Beside that, every operation whose ``tf_op`` holds ``rematted_computation``
(a checkpoint's recomputation in the backward pass) is also summed as
recomputed, whatever its scope.

**The table** a traced run prints once, however many metrics ask (the file
is parsed once: a memo by path), reads: the whole steps in the trace, the
step program's ms (its event on ``XLA Modules``), all operations' ms a step
(their sum; under the program's by the gaps inside it); then one line a
scope, longest first, with its ms a step and its share of all operations,
and under it its five longest operations by ``xplane.op_label`` (HLO name
without its number, type and shape of the first result: the ledger's
``breakdown`` names them so), ms a step each; ``unscoped`` last with ten:
what stands there above 1 % wants a scope in the program.  The scopes'
ms sum to "all operations".  The last lines give the recomputed share and
the ``moe/route`` + ``/experts`` + ``/combine`` share with each operation
once, to read beside ``moe_routed_device_pct``; then the seconds the
reader took (the one cost of this split, paid after the window of a traced
run and never in an untraced one).

The metric files ``metrics/step_*_device_pct.py`` are each a call of
:func:`share` or :func:`recomputed_share`.  Both return None without a
trace, without a whole ``jit_step`` program on chip 0, or where no
operation of a step is under the scopes asked for (a program without
them, as every one before PR 38).
"""
from __future__ import annotations

import re
import time
from dataclasses import dataclass, field

from benchmark import xplane
from benchmark.metrics.moe_routed_device_pct import (
    GROUPED,
    STEP,
    operation_strings,
)

#: control-flow operations whose event spans the events of their bodies
CONTAINER = re.compile(r"^%?(while|conditional|call|cond)[\w.\-]* = ")
RECOMPUTED = "rematted_computation"
COLLECTIVE = "step/collective"
UNSCOPED = "unscoped"
ROUTED = ("moe/route", "moe/experts", "moe/combine")


def vocabulary() -> tuple:
    """The program's list of scope names (``timeline.SCOPE_*``)."""
    from autodist_tpu.telemetry import timeline

    return tuple(sorted(value for name, value in vars(timeline).items()
                        if name.startswith("SCOPE_")))


@dataclass
class Table:
    steps: int
    program_s: float                 # the step programs' own events
    total_s: float = 0.0             # all operations, each once
    recomputed_s: float = 0.0
    by_scope: dict = field(default_factory=dict)     # scope -> seconds
    ops: dict = field(default_factory=dict)          # scope -> label -> s

    def share(self, *scopes):
        """Percent of all operations under ``scopes``; None where none of
        them holds an operation."""
        found = [self.by_scope[s] for s in scopes if s in self.by_scope]
        return 100.0 * sum(found) / self.total_s if found else None

    def render(self) -> str:
        ms = 1e3 / self.steps
        out = [f"step scopes: {self.steps} whole steps; step program "
               f"{self.program_s * ms:.1f} ms; all operations "
               f"{self.total_s * ms:.1f} ms a step, each once"]
        named = sorted((s for s in self.by_scope if s != UNSCOPED),
                       key=lambda s: -self.by_scope[s])
        for scope in named + [UNSCOPED] * (UNSCOPED in self.by_scope):
            out.append(f"  {scope}: {self.by_scope[scope] * ms:.1f} ms a "
                       f"step, {self.share(scope):.2f} %")
            longest = sorted(self.ops[scope].items(), key=lambda kv: -kv[1])
            for label, s in longest[:10 if scope == UNSCOPED else 5]:
                out.append(f"      {s * ms:7.2f}  {label}")
        out.append(f"  recomputed ({RECOMPUTED}, whatever the scope): "
                   f"{self.recomputed_s * ms:.1f} ms a step, "
                   f"{100.0 * self.recomputed_s / self.total_s:.2f} %")
        routed = self.share(*ROUTED)
        if routed is not None:
            out.append(f"  {' + '.join(ROUTED)}: {routed:.2f} % of all "
                       f"operations, each once (moe_routed_device_pct "
                       f"divides by a total that holds a conditional's "
                       f"branch twice)")
        return "\n".join(out)


def build(red, strings: dict):
    """The :class:`Table` of a reduction's first chip, ``strings`` being
    ``operation_strings`` of the same file; None without a whole step
    program or an operation inside one."""
    if not red.chips:
        return None
    chip = red.chips[0]
    runs = [(s, e) for n, s, e in chip.modules if re.search(STEP, n)]
    if not runs:
        return None
    named = re.compile("|".join(rf"\b{re.escape(n)}\b"
                                for n in vocabulary()))
    table = Table(len(runs), sum(e - s for s, e in runs) / 1e9)
    where = {}
    for name, start, end in chip.ops:
        if CONTAINER.match(name) or not any(
                a <= start and end <= b for a, b in runs):
            continue
        if name not in where:
            tf_op = strings.get(name, {}).get("tf_op", "")
            found = named.findall(tf_op)
            where[name] = (
                COLLECTIVE if xplane.COLLECTIVE.match(name)
                else found[-1] if found
                else ROUTED[1] if GROUPED.match(name) else UNSCOPED,
                RECOMPUTED in tf_op, xplane.op_label(name))
        scope, recomputed, label = where[name]
        seconds = (end - start) / 1e9
        table.total_s += seconds
        table.recomputed_s += seconds * recomputed
        table.by_scope[scope] = table.by_scope.get(scope, 0.0) + seconds
        ops = table.ops.setdefault(scope, {})
        ops[label] = ops.get(label, 0.0) + seconds
    return table if table.total_s else None


_tables: dict = {}       # a trace's path -> its Table (or None), built once


def table(run):
    """The traced run's :class:`Table`, printed when it is first built."""
    red = run.trace_reduction
    path = run.tracer.xplane_path() if red is not None else None
    if path is None:
        return None
    if path not in _tables:
        began = time.perf_counter()
        _tables[path] = build(red, operation_strings(path))
        if _tables[path] is not None:
            print(_tables[path].render(), flush=True)
        print(f"step scopes: read in {time.perf_counter() - began:.2f} s",
              flush=True)
    return _tables[path]


def share(run, *scopes):
    """Percent of a step's operations under ``scopes``, or None."""
    found = table(run)
    return None if found is None else found.share(*scopes)


def recomputed_share(run):
    """Percent of a step's operations that recompute a checkpoint's
    forward (0 where the step has no checkpoint), or None."""
    found = table(run)
    return None if found is None \
        else 100.0 * found.recomputed_s / found.total_s
