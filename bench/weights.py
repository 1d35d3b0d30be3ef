"""Seeded random weights, made on the device in one jitted call.

A family module (``bench/families/<family>.py``) states its tree as leaf
shapes and a leaf rule; :func:`make_tree` draws every leaf from one key
made from the seed.  The harness checks the tree against the program's own
parameter shapes before packing, and the plain reference makes the same
tree again from the same seed after the program's state is freed, so it
takes nothing the program has made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def jax_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    word = np.random.default_rng(seed).integers(0, 2**31 - 1)
    return jax.random.PRNGKey(int(word))


def is_shape(x) -> bool:
    """A leaf of a shapes tree: a tuple of dimensions."""
    return isinstance(x, tuple)


def make_tree(shapes: dict, seed: int, dtype: str, leaf_rule) -> dict:
    """The tree of ``shapes`` (leaves are shape tuples) from ``seed``, in
    ``dtype``: ``leaf_rule(key, path, shape, dtype)`` draws the leaf at
    ``path`` (``jax.tree_util.keystr`` of it), each from its own key."""
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=is_shape)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes, is_leaf=is_shape)[0]]
    dt = jnp.dtype(dtype)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            leaf_rule(k, p, s, dt) for k, p, s in zip(keys, paths, leaves)])

    return build(jax_key(seed))
