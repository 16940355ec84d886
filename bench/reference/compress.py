"""Plain reference of the C-HSGD message compression (paper Sec. VII-A1).

Each message leaf is read as rows of its last axis. A row keeps its k
largest-magnitude entries, k = max(1, round(k_frac * width)) (ties at the
k-th magnitude are all kept), and the kept entries are snapped to a uniform
grid of ``levels`` points spanning their own [min, max]; every other entry
is 0. Exact top-k by sorting, in float32; written from the description
above, not from the program's threshold search.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def keep_count(width: int, k_frac: float) -> int:
    return max(1, int(round(k_frac * width))) if 0.0 < k_frac < 1.0 else width


def compress_rows(rows: jnp.ndarray, k: int, levels: int) -> jnp.ndarray:
    """[rows, n] float32 -> compressed rows."""
    n = rows.shape[-1]
    x = rows.astype(jnp.float32)
    mag = jnp.abs(x)
    if k < n:
        kth = jnp.sort(mag, axis=-1)[:, n - k:n - k + 1]  # k-th largest
        kept = mag >= kth
    else:
        kept = jnp.ones(x.shape, bool)
    y = jnp.where(kept, x, 0.0)
    if levels > 1:
        lo = jnp.min(jnp.where(kept, x, jnp.inf), axis=-1, keepdims=True)
        hi = jnp.max(jnp.where(kept, x, -jnp.inf), axis=-1, keepdims=True)
        step = jnp.maximum(hi - lo, 1e-12) / (levels - 1)
        y = jnp.where(kept, jnp.round((x - lo) / step) * step + lo, 0.0)
    return y


_compress_rows_jit = jax.jit(compress_rows, static_argnums=(1, 2))


def compress_tree(tree, k_frac: float, levels: int):
    """Every leaf of a message, row by row (one jitted call per leaf)."""
    def leaf(x):
        n = x.shape[-1]
        out = _compress_rows_jit(x.reshape(-1, n), keep_count(n, k_frac), levels)
        return out.reshape(x.shape).astype(x.dtype)

    return jax.tree.map(leaf, tree)
