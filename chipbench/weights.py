"""Weights from the seed, made on the device in one jitted call.

The tree's nesting, shapes and initial scales come from the plain
reference (``references/<name>.py``: ``param_shapes``); the program is
handed the same tree cast to its parameter dtype, and the reference
makes it again from the same seed once the program's state is freed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A PRNG key from any non-negative whole number (64 bits and more)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def make(seed: int, shapes, dtype, is_leaf, out_shardings=None):
    """Materialize a (shape, init) tree: normal leaves draw from their own
    key, folded in from the leaf's index; "gate" leaves are 1 on the first
    n entries and 0 after (float32, whatever ``dtype``); norm scales are
    1, biases 0."""
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=is_leaf)

    def build(key):
        out = []
        for i, (shape, init) in enumerate(leaves):
            if init == "ones":
                a = jnp.ones(shape, jnp.float32)
            elif init == "zeros":
                a = jnp.zeros(shape, jnp.float32)
            elif init[0] == "gate":          # structural: stays float32
                out.append((jnp.arange(shape[0]) < init[1]).astype(
                    jnp.float32))
                continue
            else:
                a = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32) * init[1]
            out.append(a.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    fn = jax.jit(build, out_shardings=out_shardings)
    return fn(base_key(seed))
