"""Shared Pallas TPU kernel plumbing (interpret mode, tiling, blocks).

Every Pallas kernel in the repo re-derived the same three decisions —
when to run the interpreter (off-TPU CPU tests), how to pad a dimension
to an MXU-tileable length, and how to pick a block edge that divides the
(padded) extent — first in ``ops/flash_attention.py``, then again in
``ops/quant.py``.  The fused-kernel suite (``ops/fused_kernels.py``)
would have made a third copy; this module is the single definition all
of them import, so a tiling-policy fix lands everywhere at once.

The policies themselves are unchanged from the flash-attention
originals (measured defaults documented there):

* :func:`use_interpret` — Pallas interpret mode is selected
  automatically whenever the first device is not a TPU, so the CPU test
  mesh exercises the exact kernel bodies the TPU compiles; the resolved
  mode is logged once, with the platform, so a run's log says whether
  its kernels were compiled;
* :func:`pad_len` — compiled Pallas wants (8, 128)-aligned tiles:
  lengths ≤ 128 round up to a multiple of 8 (the whole extent is one
  block), longer ones to a multiple of :data:`TILE`; interpret mode has
  no constraint and pads nothing;
* :func:`pick_block` — largest block ≤ target dividing the extent,
  preferring multiples of the MXU tile;
* :func:`pad_to` — plain round-up, the unit everything else composes.
"""
from __future__ import annotations

import functools
from typing import Optional

#: MXU lane quantum: pad unit and block alignment for every TPU kernel.
TILE = 128

#: f32 sublane quantum (min tile is (8, 128) for float32).
SUBLANE = 8


@functools.cache
def use_interpret() -> bool:
    """Run Pallas in interpret mode?  Resolved from the backend — off-TPU
    (the CPU test mesh) interprets, on TPU the kernel compiles.  Resolved
    once per process, and logged then with the platform."""
    import jax

    from autodist_tpu.utils import logging

    platform = jax.devices()[0].platform
    interpret = platform != "tpu"
    logging.info("Pallas kernels: %s (platform %s)",
                 "interpret mode" if interpret else "compiled", platform)
    return interpret


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret`` if explicitly given, else :func:`use_interpret` —
    the per-op knob every public kernel entry point exposes."""
    return use_interpret() if interpret is None else bool(interpret)


_VMEM_DEFAULT = 16 << 20   # Mosaic's scoped-VMEM limit on the v5e
_VMEM_MAX = 100 << 20      # of the v5e's 128 MiB


def vmem_limit(need: int) -> Optional[int]:
    """``vmem_limit_bytes`` for a kernel that holds ``need`` bytes: None
    (Mosaic's default) where they fit it, else ``need`` up to what the
    v5e's VMEM leaves a kernel."""
    return None if need <= _VMEM_DEFAULT else min(need, _VMEM_MAX)


def widest_tile(n: int, fits) -> int:
    """The widest tile of ``n`` (whole :data:`TILE`-lane tiles that divide
    it) with ``fits(tile)``, one lane tile if none does; all of ``n`` where
    it is no whole number of lanes."""
    if n % TILE:
        return n
    return max((w for w in range(TILE, n + 1, TILE)
                if n % w == 0 and fits(w)), default=TILE)


def pad_to(n: int, m: int) -> int:
    """``n`` rounded up to the next multiple of ``m``."""
    return -(-int(n) // int(m)) * int(m)


def pad_len(t: int, interpret: bool) -> int:
    """Sequence/vector length after padding to an MXU-tileable length.
    Compiled Pallas requires (8, 128)-aligned tiles; interpret mode has
    no such constraint.  ≤128 → next multiple of 8 (the whole extent is
    one block); >128 → next multiple of 128 (a 128-multiple block always
    divides)."""
    if interpret:
        return t
    if t <= TILE:
        return pad_to(t, SUBLANE)
    return pad_to(t, TILE)


def pick_block(t: int, target: int) -> int:
    """Largest block ≤ ``target`` dividing ``t``, preferring multiples
    of the MXU tile (``pad_len`` guarantees a 128-multiple divisor
    exists on the compiled path; tiny interpret-mode extents fall back
    to any divisor)."""
    b = min(t, target)
    for cand in range(b - b % TILE, 0, -TILE):
        if t % cand == 0:
            return cand
    while t % b:
        b -= 1
    return b
