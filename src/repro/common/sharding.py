"""Sharding helpers: logical-axis rules -> NamedSharding, plus mesh-aware utils.

We use a MaxText-style logical axis annotation scheme: every parameter and
activation is tagged with logical axis names; a rule table maps logical names
to mesh axes. Changing the sharding scheme (e.g. during §Perf hillclimbing)
means swapping the rule table, not touching model code.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Default logical->physical rules for the production mesh.
# "data" carries the horizontal (group) partition of the paper;
# "model" carries the vertical partition + tensor parallelism;
# "pod" is the second horizontal tier (multi-pod).
DEFAULT_RULES: Dict[str, Optional[Tuple[str, ...]]] = {
    "batch": ("pod", "data"),
    "group": ("pod", "data"),
    # FSDP: parameter d_model dims shard over "data"; activations tag "batch"
    # first so the duplicate-axis filter keeps activations data-sharded on
    # batch while parameters ZeRO-shard on embed. NOT sharded over "pod" —
    # each pod holds its own HSGD local model replica (see DESIGN §2).
    "embed": ("data",),
    "seq": None,
    "cache_seq": ("model",),  # decode KV caches shard their length over model
    "mlp": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": None,
    "vocab": ("model",),
    "experts": ("model",),
    "expert_tokens": ("data",),
    "expert_mlp": None,
    "ssm_inner": ("model",),
    "ssm_state": None,
    "conv": None,
    "device_slot": None,  # tier-1 vmapped devices stay local
    "pod_group": ("pod",),  # per-pod HSGD local-model replicas (leading G dim)
    "pod_batch": ("pod", "data"),  # inference batch scale-out across pods
    "stack": None,  # scan-stacked layer dimension
}

# Fully-replicated-model variant (pure data parallel) for small models.
DP_ONLY_RULES: Dict[str, Optional[Tuple[str, ...]]] = {k: None for k in DEFAULT_RULES}
DP_ONLY_RULES["batch"] = ("pod", "data", "model")
DP_ONLY_RULES["group"] = ("pod", "data", "model")


def logical_to_spec(axes: Sequence[Optional[str]], rules=None, mesh: Optional[Mesh] = None) -> P:
    """Map a tuple of logical axis names to a PartitionSpec via the rules."""
    rules = rules or DEFAULT_RULES
    mesh_axes = set(mesh.axis_names) if mesh is not None else None
    spec = []
    used = set()
    for ax in axes:
        if ax is None:
            spec.append(None)
            continue
        phys = rules.get(ax)
        if phys is None:
            spec.append(None)
            continue
        if mesh_axes is not None:
            phys = tuple(p for p in phys if p in mesh_axes)
        phys = tuple(p for p in phys if p not in used)
        used.update(phys)
        if not phys:
            spec.append(None)
        elif len(phys) == 1:
            spec.append(phys[0])
        else:
            spec.append(phys)
    return P(*spec)


def shard_tree(tree_axes, mesh: Mesh, rules=None):
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings."""
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, logical_to_spec(axes, rules, mesh)),
        tree_axes,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x),
    )


def mesh_context(mesh: Mesh):
    """``jax.set_mesh(mesh)``: use for every ``with <mesh>:`` block."""
    return jax.set_mesh(mesh)


def group_sharding(shape, mesh: Mesh, rules=None) -> NamedSharding:
    """NamedSharding putting a leading group axis M on the mesh's horizontal
    axes (logical "group" rule), everything else replicated.

    Used to shard HSGDState / federated data leaves ([M, ...]) so eq. (1)/(2)
    aggregations lower to collectives. Falls back to full replication when
    the leading dim does not divide the mesh axes (trivial-mesh path).
    """
    axes = ("group",) + (None,) * (max(len(shape), 1) - 1)
    spec = logical_to_spec(axes[: len(shape)], rules, mesh)
    spec = divisible_spec(shape, spec, mesh)
    return NamedSharding(mesh, spec)


def divisible_spec(shape, spec: P, mesh: Mesh) -> P:
    """Drop mesh axes from a spec wherever the dim is not divisible.

    Keeps dry-runs robust when a reduced config's dim < mesh axis size.
    """
    new = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None:
            new.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        new.append(entry if dim % size == 0 and dim >= size else None)
    return P(*new)


def constrain(x, axes, rules=None):
    """with_sharding_constraint by logical axes (no-op outside a mesh ctx).

    Mesh- and shape-aware: absent mesh axes are filtered (not the whole
    entry), non-divisible dims are left unconstrained, and a rank mismatch
    is a silent no-op (some call sites see flattened tensors).
    """
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty:  # pragma: no cover
            return x
    except Exception:  # pragma: no cover
        return x
    if len(axes) != x.ndim:
        return x
    rules = rules or DEFAULT_RULES
    names = set(mesh.axis_names)
    entries = []
    used = set()
    for dim, ax in zip(x.shape, axes):
        phys = rules.get(ax) if ax is not None else None
        if phys is None:
            entries.append(None)
            continue
        phys = tuple(p for p in phys if p in names and p not in used)
        size = 1
        for p in phys:
            size *= mesh.shape[p]
        if not phys or size == 1 or dim % size != 0:
            entries.append(None)
            continue
        used.update(phys)
        entries.append(phys if len(phys) > 1 else phys[0])
    return jax.lax.with_sharding_constraint(x, P(*entries))


def _axes_in(mesh, entry) -> bool:
    names = set(mesh.axis_names)
    axes = entry if isinstance(entry, tuple) else (entry,)
    return all(a in names for a in axes)


import contextlib

_WEIGHT_MODE = "gather"


@contextlib.contextmanager
def weight_mode(mode: str):
    """'gather' (train/prefill: ZeRO-3 gather-at-use) or 'fsdp' (decode:
    activations are tiny, so leave weights sharded and let XLA compute
    partial matmuls + reduce — §Perf iteration 2)."""
    global _WEIGHT_MODE
    prev = _WEIGHT_MODE
    _WEIGHT_MODE = mode
    try:
        yield
    finally:
        _WEIGHT_MODE = prev


def use_weight(w, axes, rules=None):
    if _WEIGHT_MODE == "fsdp":
        return w
    """ZeRO-3 weight use: parameters are STORED FSDP-sharded over "data"
    (their 'embed'-like dims), but at their use site we constrain them to the
    gathered layout (data dropped, tensor-parallel axes kept). XLA then emits
    one small weight all-gather per step instead of re-sharding activations —
    the difference between 100s-of-GB activation all-gathers and MB-scale
    weight gathers (see DESIGN §Perf iteration 0).
    """
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty:  # pragma: no cover
            return w
    except Exception:  # pragma: no cover
        return w
    rules = rules or DEFAULT_RULES
    names = set(mesh.axis_names)
    entries = []
    used = set()
    for dim, ax in zip(w.shape, axes):
        phys = rules.get(ax) if ax is not None else None
        if phys is None:
            entries.append(None)
            continue
        phys = tuple(p for p in phys if p != "data" and p in names and p not in used)
        size = 1
        for p in phys:
            size *= mesh.shape[p]
        if not phys or size == 1 or dim % size != 0:
            entries.append(None)
            continue
        used.update(phys)
        entries.append(phys if len(phys) > 1 else phys[0])
    return jax.lax.with_sharding_constraint(w, P(*entries))
