"""Seeded random weights, made on the device in one jitted call.

The tree has the layout the program's dense GQA language model reads
(``embed``, layer-stacked ``blocks``, ``final_norm``); the harness checks
it against the program's own parameter shapes before packing.  The plain
reference (``bench/reference.py``) makes the same tree again from the same
seed after the program's state is freed, so it takes nothing the program
has made.

Distributions follow the usual initialisation of such a model: a normal
embedding of standard deviation 0.02, and truncated-normal projections
scaled by the inverse square root of their fan-in, so that activations
keep a realistic scale through the depth.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def jax_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    word = np.random.default_rng(seed).integers(0, 2**31 - 1)
    return jax.random.PRNGKey(int(word))


def shapes(model: dict) -> dict:
    """Leaf shapes of the tree, keyed like the tree."""
    L, d, f, v = (model["n_layers"], model["d_model"], model["d_ff"],
                  model["vocab"])
    qd = model["n_heads"] * model["head_dim"]
    kvd = model["kv_heads"] * model["head_dim"]
    norm = (lambda *lead: {"scale": (*lead, d)}) \
        if model["norm"] == "rmsnorm" else (lambda *lead: {})
    return {
        "embed": {"table": (v, d)},
        "blocks": {
            "attn_norm": norm(L),
            "mlp_norm": norm(L),
            "attn": {"q": {"w": (L, d, qd)}, "k": {"w": (L, d, kvd)},
                     "v": {"w": (L, d, kvd)}, "o": {"w": (L, qd, d)}},
            "mlp": {"gate": {"w": (L, d, f)}, "up": {"w": (L, d, f)},
                    "down": {"w": (L, f, d)}},
        },
        "final_norm": norm(),
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def make(model: dict, seed: int, dtype: str = "float32") -> dict:
    """The weight tree of ``model`` from ``seed``, in ``dtype``."""
    tree = shapes(model)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_shape)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_shape)[0]]
    dt = jnp.dtype(dtype)

    def leaf(key, path, shp):
        if path.endswith("['scale']"):
            return jnp.ones(shp, dt)
        if path.startswith("['embed']"):
            return (jax.random.normal(key, shp, jnp.float32) * 0.02).astype(dt)
        fan_in = shp[-2]
        w = jax.random.truncated_normal(key, -2.0, 2.0, shp, jnp.float32)
        return (w * fan_in ** -0.5).astype(dt)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(
            treedef, [leaf(k, p, s) for k, p, s in zip(keys, paths, leaves)])

    return build(jax_key(seed))
