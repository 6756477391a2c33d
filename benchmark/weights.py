"""Seeded weights, made by the benchmark on the device in one jitted call.

The program is asked only for the SHAPES of its parameter tree
(``jax.eval_shape(spec.init)``).  The values are the benchmark's: GPT-2's
own initializer, normal(0, 0.02) for every matrix and both embedding
tables, LayerNorm scales 1, each leaf in the type the program keeps it in
(``transformer_lm(dtype=bfloat16)``: bfloat16 tables, float32 layers).
The same tree goes to the program and, read by name, to the reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

INIT_STD = 0.02


def make_weights(shapes, seed: int, sharding=None):
    """``shapes``: a pytree of ShapeDtypeStruct.  ``seed``: any integer
    (folded to the 64 bits a PRNG key takes)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = str(getattr(path[-1], "key", path[-1]))
            if name == "scale":
                out.append(jnp.ones(leaf.shape, leaf.dtype))
            else:
                out.append((INIT_STD * jax.random.normal(
                    jax.random.fold_in(key, i), leaf.shape,
                    jnp.float32)).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    key = jax.random.key(int(seed) % (1 << 63), impl="threefry2x32")
    return jax.jit(build, out_shardings=sharding)(key)
