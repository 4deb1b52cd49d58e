"""Random serving weights from the seed, made on the device in one jit.

The tree's structure and leaf shapes are the program's own
(``jax.eval_shape`` of ``Model.init``, nothing computed); the values are
drawn here: RMSNorm gains 1, the embedding N(0, 0.02^2), every projection
N(0, 1/fan_in), all held in the configuration's serving dtype.  The same
tree feeds the program and, after the window, the plain reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def make(model, key32: int, dtype):
    return jax.jit(builder(model, dtype))(jax.random.PRNGKey(key32))


def builder(model, dtype):
    """The function of a PRNG key that makes the whole weight tree."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        keys = jax.random.split(key, len(flat))
        leaves = []
        for (path, sd), k in zip(flat, keys):
            name = _leaf_name(path)
            if name == "g":
                leaves.append(jnp.ones(sd.shape, dtype))
                continue
            scale = 0.02 if name == "e" else sd.shape[-2] ** -0.5
            leaves.append((jax.random.normal(k, sd.shape, jnp.float32)
                           * scale).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build
