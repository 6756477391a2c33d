"""Where the persistent XLA compilation cache lives.

Called by the two scripts that run on the chip (``chip_smoke.py`` and
``benchmark/run.py``) before their first use of JAX, and by nothing else: a
library import must not move a process's cache.
"""
from __future__ import annotations

import os

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Return the cache directory, setting it only if nobody else has.

    With ``JAX_COMPILATION_CACHE_DIR`` in the environment this does
    nothing at all: jax reads that variable itself, and a machine that
    sets it keeps the directory between runs.  Otherwise the cache goes
    to ``<repo>/.jax_cache``: one fixed path, because the path is part of
    what a later process must repeat to find the entries, so it is never
    derived from a pid, a temporary name or the time."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_REPO, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
