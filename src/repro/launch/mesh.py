"""Device meshes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

In the HSGD mapping (DESIGN §2): "pod" carries the hospital-patient groups
(tier-3 horizontal — aggregated every P steps), "data" carries batch/FSDP
within a group (tier-1 — the intra-group device aggregation), and "model"
carries the vertical partition + tensor parallelism (tier-2 — the ζ exchange
every Q steps).

Every mesh is built with ``Auto`` axis types: the federation code places its
arrays with ``NamedSharding``s and lets the compiler propagate shardings
through vmaps and scans, which ``jax.make_mesh``'s default ``Explicit`` axes
(jax >= 0.7) reject.

Defined as functions, never module-level constants: importing this module
must not touch jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, multi_pod: bool = False):
    """Small mesh for CI-sized dry-run tests (requires >= n devices)."""
    if multi_pod:
        return make_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return make_mesh((n_data, n_model), ("data", "model"))
