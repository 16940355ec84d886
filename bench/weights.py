"""Weights made by the benchmark from the seed, on the device, in one jitted
call. The program under test and the plain reference both receive these
arrays, so the reference takes no weights that the program made.

Only the tree's structure and shapes come from the program (through
``jax.eval_shape``); the values follow one rule per leaf name:

* ``b``, ``*_b``, ``bias``  -> zeros
* ``scale``                 -> ones
* ``table`` (embeddings)    -> normal * 0.02
* everything else           -> truncated normal * fan_in ** -0.5, where the
  fan-in is the product of the leaf's input axes (see ``_fan_in``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def _fan_in(path, shape) -> int:
    names = [str(getattr(p, "key", p)) for p in path]
    stacked = "layers" in names  # leading [L] axis of a layer stack
    s = shape[1:] if stacked else shape
    leaf = names[-1]
    if leaf in ("wq", "wk", "wv"):  # [d, heads, head_dim]
        return s[0]
    if leaf == "wo":  # [heads, head_dim, d]
        return s[0] * s[1]
    return int(math.prod(s[:-1])) if len(s) > 1 else s[0]


def make(key, shapes, dtype=jnp.float32):
    """Pytree like ``shapes`` (ShapeDtypeStructs) filled from ``key``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def build(key):
        out = []
        for i, (path, s) in enumerate(flat):
            name = _name(path)
            k = jax.random.fold_in(key, i)
            if name in ("b", "bias") or name.endswith("_b"):
                x = jnp.zeros(s.shape, jnp.float32)
            elif name == "scale":
                x = jnp.ones(s.shape, jnp.float32)
            elif name == "table":
                x = jax.random.normal(k, s.shape, jnp.float32) * 0.02
            else:
                x = jax.random.truncated_normal(k, -2.0, 2.0, s.shape, jnp.float32)
                x = x * (_fan_in(path, s.shape) ** -0.5)
            out.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return build(key)
