"""Core layer primitives + the Spec param-declaration system.

Every layer declares its parameters once as a nested dict of ``Spec``s
(shape, logical axes, initializer). From that single source of truth we derive
  * ``init_params``   — concrete PRNG-initialized arrays,
  * ``abstract_params`` — ShapeDtypeStructs (dry-run, no allocation),
  * ``axes_tree``     — logical-axis tuples -> NamedShardings via common.sharding.
Apply functions are plain JAX functions over the params dict.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Spec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"  # normal | zeros | ones | embed
    scale: Optional[float] = None

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_spec(x) -> bool:
    return isinstance(x, Spec)


def _fan_in(shape) -> int:
    return int(np.prod(shape[:-1])) if len(shape) > 1 else shape[0]


def init_params(specs, key, dtype=jnp.float32):
    """Initialize a pytree of Specs into concrete arrays."""
    leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for spec, k in zip(leaves, keys):
        if spec.init == "zeros":
            arr = jnp.zeros(spec.shape, dtype)
        elif spec.init == "ones":
            arr = jnp.ones(spec.shape, dtype)
        elif spec.init == "embed":
            s = spec.scale if spec.scale is not None else 1.0
            arr = (jax.random.normal(k, spec.shape) * s).astype(dtype)
        else:  # truncated-normal fan-in scaled (lecun)
            s = spec.scale if spec.scale is not None else 1.0 / math.sqrt(max(_fan_in(spec.shape), 1))
            arr = (jax.random.truncated_normal(k, -2.0, 2.0, spec.shape) * s).astype(dtype)
        out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out)


def abstract_params(specs, dtype=jnp.float32):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype), specs, is_leaf=is_spec
    )


def axes_tree(specs):
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=is_spec)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_specs(d: int) -> Dict[str, Spec]:
    return {"scale": Spec((d,), ("embed",), "ones")}


def rmsnorm(params, x, eps: float = 1e-6):
    # (§Perf iteration 8, refuted: keeping x in bf16 through the norm did NOT
    # remove XLA's hoisted f32 stack conversion and cost ~1% extra bytes —
    # the standard f32-upcast norm is retained.)
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dtype)


def layernorm_specs(d: int) -> Dict[str, Spec]:
    return {"scale": Spec((d,), ("embed",), "ones"), "bias": Spec((d,), ("embed",), "zeros")}


def layernorm(params, x, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(dtype)


def norm_specs(kind: str, d: int):
    return rmsnorm_specs(d) if kind == "rmsnorm" else layernorm_specs(d)


def apply_norm(kind: str, params, x):
    return rmsnorm(params, x) if kind == "rmsnorm" else layernorm(params, x)


# ---------------------------------------------------------------------------
# Dense / embedding
# ---------------------------------------------------------------------------


def dense_specs(d_in: int, d_out: int, axes: Tuple[Optional[str], Optional[str]], scale=None):
    return {"w": Spec((d_in, d_out), axes, "normal", scale)}


def dense(params, x):
    w = params["w"]
    return jnp.einsum("...d,df->...f", x, w.astype(x.dtype))


def embed_specs(vocab: int, d: int):
    # vocab shards over "model"; d replicated (a data-sharded d here would
    # force XLA to un-shard the batch at every lookup/unembed — see DESIGN).
    return {"table": Spec((vocab, d), ("vocab", None), "embed", 0.02)}


def embed(params, ids):
    return jnp.take(params["table"], ids, axis=0)


def unembed(params, x):
    """Tied-embedding readout."""
    t = params["table"].astype(x.dtype)
    return jnp.einsum("...d,vd->...v", x, t)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


def squared_relu(x):
    r = jax.nn.relu(x)
    return r * r


ACTIVATIONS = {
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "relu": jax.nn.relu,
    "squared_relu": squared_relu,
}


# ---------------------------------------------------------------------------
# RoPE (standard + M-RoPE)
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: [..., S, H, D]; positions: broadcastable to [..., S]."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)  # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos = jnp.cos(angles)[..., None, :]  # [..., S, 1, D/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_partial_rope(x, positions, theta: float, rot: int):
    """RoPE over the first ``rot`` channels of each head (frequencies from
    ``rot``, not the head width); the rest pass through unrotated."""
    if rot == x.shape[-1]:
        return apply_rope(x, positions, theta)
    return jnp.concatenate([apply_rope(x[..., :rot], positions, theta), x[..., rot:]], axis=-1)


def apply_mrope(x, positions_3d, sections: Tuple[int, ...], theta: float = 10000.0):
    """Qwen2-VL multimodal RoPE.

    positions_3d: [..., S, 3] (temporal, height, width position ids).
    sections: split of head_dim/2 frequency slots over the 3 position kinds.
    """
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)  # [D/2]
    # build per-slot position selector
    sec = jnp.concatenate(
        [jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sections)]
    )  # [D/2]
    # gather: pos[..., s, j] = positions_3d[..., s, sec[j]]
    pos = jnp.einsum(
        "...sk,jk->...sj",
        positions_3d.astype(jnp.float32),
        jax.nn.one_hot(sec, 3, dtype=jnp.float32),
    )  # [..., S, D/2]
    angles = pos * freqs
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def text_positions_3d(positions):
    """Text-only M-RoPE degenerates to identical ids on all 3 channels."""
    return jnp.stack([positions, positions, positions], axis=-1)
