"""Federation bookkeeping: group/device sampling and weighted aggregation.

Implements eq. (1) (local aggregation over the sampled device subset A_m) and
eq. (2) (global weighted aggregation over groups) plus the A_m / mini-batch
agreement of Algorithm 1 line 13 as jit-friendly index sampling.

Sharding: every [M, ...] tensor is tagged with the logical "group" axis (see
common/sharding.py). Under a non-trivial mesh the group axis rides the
horizontal mesh axes, so eq. (2) lowers to a cross-group reduce collective
and the broadcasts keep their outputs group-sharded instead of gathering a
replicated copy per device. On a trivial mesh every constraint is a no-op.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from repro.common.config import FederationConfig
from repro.common.sharding import constrain

LANES = 128  # the TPU vector unit's minor tile width


def _group_axes(x):
    return ("group",) + (None,) * (x.ndim - 1)


def _constrain_grouped(tree):
    """Tag the leading group axis of every [M, ...] leaf."""
    return jax.tree.map(lambda x: constrain(x, _group_axes(x)), tree)


def lane_dense(x):
    """``x`` [M, A, ..., R, C] held with its last two dims swapped in the
    layout where the per-device leaf is C < 128 wide and R >= 128 tall.

    A minor dim narrower than the lanes pads every tile, so each pass over
    the leaf moves the padding too (the cnn tower's proj [896, 64]: twice its
    bytes). Only the physical layout changes: the shape, values and arithmetic
    stay; XLA carries the layout on to the scan carries and aggregations.
    """
    if x.ndim < 4 or x.shape[-1] >= LANES or x.shape[-2] < LANES:
        return x
    order = (*range(x.ndim - 2), x.ndim - 1, x.ndim - 2)
    return with_layout_constraint(x, Layout(major_to_minor=order))


def local_aggregate(theta2_active, mask=None):
    """Eq. (1): θ2_m = mean over the sampled devices. [M, A, ...] -> [M, ...].

    ``mask`` ([M, A], 1 = real cohort member, 0 = padding slot) restricts the
    mean to the round's actual participants — the cohort path pads device
    slots to a power-of-two bucket, and padded slots must not dilute θ2_m.
    A group with an empty cohort falls back to the plain mean (its slots are
    uniform between rounds, so the fallback is exact; its global weight is
    zeroed by the scheduler anyway).
    """
    if mask is None:
        return _constrain_grouped(
            jax.tree.map(lambda x: jnp.mean(x, axis=1), theta2_active))
    w = mask.astype(jnp.float32)
    cnt = jnp.sum(w, axis=1)  # [M]
    safe = jnp.maximum(cnt, 1.0)

    def agg(x):
        wb = w.reshape(w.shape + (1,) * (x.ndim - 2)).astype(x.dtype)
        masked = jnp.sum(x * wb, axis=1) / safe.reshape(
            (-1,) + (1,) * (x.ndim - 2)).astype(x.dtype)
        plain = jnp.mean(x, axis=1)
        keep = (cnt > 0).reshape((-1,) + (1,) * (x.ndim - 2))
        return jnp.where(keep, masked, plain)

    return _constrain_grouped(jax.tree.map(agg, theta2_active))


# ---------------------------------------------------------------------------
# Secure aggregation (pairwise-mask simulation, Bonawitz-style)
# ---------------------------------------------------------------------------

# Reserved RNG stream index for pairwise masks: default_rng([seed, 4, r, m, i, j]).
# Streams 0 (registry), 1 (cohort), 2 (typical tails), 3 (faults) are taken —
# see the reprolint RP10 registry in analysis/rules.py.
SECURE_AGG_STREAM = 4
# Fixed-point fractional bits of the ℤ_{2^32} ring encoding. Exact-sum
# requirement: |Σ_i x_i| · 2^FRAC_BITS < 2^31 per coordinate, comfortably met
# by O(1)-magnitude parameters over cohorts of <= a few hundred slots.
SECURE_AGG_FRAC_BITS = 16


def secure_agg_masks(template, seed: int, round_idx: int, alive=None):
    """Pairwise antisymmetric int32 uplink masks for one round (host-side).

    ``template`` is the [M, A, ...] uplink pytree (θ2); the result has the
    same structure in int32. For each group m and alive pair i < j, a mask
    ``p`` is drawn from ``np.random.default_rng([seed, 4, round_idx, m, i, j])``
    (fresh reserved stream index — cannot collide with the registry / cohort /
    tails / fault streams) and slot i carries +p while slot j carries -p, so
    the ring sum over the alive slots cancels EXACTLY: integer addition mod
    2^32 is associative, unlike float. Dropout (PR 9 screening) is handled by
    re-keying per round over the surviving cohort — pass the survivors as
    ``alive`` [M, A] and dead slots get (and owe) no masks.
    """
    leaves, treedef = jax.tree_util.tree_flatten(template)
    M, A = leaves[0].shape[:2]
    if alive is None:
        alive_np = np.ones((M, A), bool)
    else:
        alive_np = np.asarray(alive) > 0
    nets = [np.zeros(l.shape, np.int64) for l in leaves]
    for m in range(M):
        for i in range(A):
            for j in range(i + 1, A):
                if not (alive_np[m, i] and alive_np[m, j]):
                    continue
                rng = np.random.default_rng(
                    [seed, SECURE_AGG_STREAM, round_idx, m, i, j])
                for li, l in enumerate(leaves):
                    p = rng.integers(-(2**31), 2**31, size=l.shape[2:],
                                     dtype=np.int64)
                    nets[li][m, i] += p
                    nets[li][m, j] -= p
    masks = [jnp.asarray((n & 0xFFFFFFFF).astype(np.uint32).view(np.int32))
             for n in nets]
    return jax.tree_util.tree_unflatten(treedef, masks)


def _ring_encode(x, frac_bits: int):
    return jnp.round(x.astype(jnp.float32) * (2.0 ** frac_bits)).astype(jnp.int32)


def secure_mask_uplink(theta2_active, masks, frac_bits: int = SECURE_AGG_FRAC_BITS):
    """Worker-side masking: fixed-point encode the uplink, add the pairwise
    mask with wrapping int32 addition. The result is what leaves the device —
    each slot's payload is uniform over the ring (mask-one-time-pad), so a
    single masked uplink is statistically uninformative about its θ2."""
    return jax.tree.map(
        lambda x, m: _ring_encode(x, frac_bits) + m, theta2_active, masks)


def secure_local_aggregate(masked_uplink, like, mask=None,
                           frac_bits: int = SECURE_AGG_FRAC_BITS):
    """Eq. (1) over ring-masked uplinks: [M, A, ...] int32 -> [M, ...] float.

    The server sums the masked integers along the device axis (wrapping mod
    2^32 — exact and associative, so the antisymmetric masks cancel to the
    bit) and only then decodes to float and divides by the participant count.
    ``like`` supplies the output dtype per leaf; ``mask`` [M, A] restricts the
    sum to the round's real cohort slots (a group with an empty cohort
    returns zeros — its global weight is zeroed upstream, matching the
    ``local_aggregate`` contract). Bit-parity with the unmasked ring pipeline
    is exact; agreement with the plain float ``local_aggregate`` holds to the
    2^-frac_bits fixed-point resolution.
    """
    leaves, treedef = jax.tree_util.tree_flatten(masked_uplink)
    like_leaves = jax.tree_util.tree_leaves(like)
    M, A = leaves[0].shape[:2]
    if mask is None:
        w = jnp.ones((M, A), jnp.int32)
    else:
        w = (mask > 0).astype(jnp.int32)
    cnt = jnp.sum(w, axis=1)  # [M]
    safe = jnp.maximum(cnt, 1).astype(jnp.float32)
    out = []
    for x, ref in zip(leaves, like_leaves):
        wb = w.reshape(w.shape + (1,) * (x.ndim - 2))
        ring_sum = jnp.sum(x * wb, axis=1)  # wrapping int32: masks cancel
        dec = ring_sum.astype(jnp.float32) / (2.0 ** frac_bits)
        mean = dec / safe.reshape((-1,) + (1,) * (dec.ndim - 1))
        keep = (cnt > 0).reshape((-1,) + (1,) * (dec.ndim - 1))
        out.append(jnp.where(keep, mean, 0.0).astype(ref.dtype))
    return _constrain_grouped(jax.tree_util.tree_unflatten(treedef, out))


def worker_sqnorm(tree, lead: int):
    """Σ_leaves ‖·‖² per worker: [M, ...] -> [M] (lead=1) or
    [M, A, ...] -> [M, A] (lead=2). NaN/Inf anywhere in a worker's slice
    poisons its entry, so ``isfinite(worker_sqnorm(g))`` is the one-reduction
    finite-value screen."""
    per = jax.tree.map(
        lambda x: jnp.sum((x * x).astype(jnp.float32),
                          axis=tuple(range(lead, x.ndim))), tree)
    return sum(jax.tree_util.tree_leaves(per))


def masked_median_values(v, w):
    """Median of the ``w > 0`` entries along axis 1: [M, A] -> [M].

    Excluded slots sort to the end behind a dtype-max sentinel; a row with no
    selected entry returns the sentinel (callers guard on their own count).
    """
    big = jnp.asarray(jnp.finfo(v.dtype).max, v.dtype)
    s = jnp.sort(jnp.where(w > 0, v, big), axis=1)
    cnt = jnp.sum((w > 0).astype(jnp.int32), axis=1)
    lo = jnp.maximum((cnt - 1) // 2, 0)
    hi = jnp.maximum(cnt // 2, 0)
    take = lambda i: jnp.take_along_axis(s, i[:, None], axis=1)[:, 0]
    med = 0.5 * (take(lo) + take(hi))
    return jnp.where(cnt > 0, med, big)


def _robust_center(x, w, method: str, trim_frac: float):
    """Robust masked center along the device axis: [M, A, ...] -> [M, ...].

    ``w`` [M, A] selects the contributing slots. "mean" is the masked mean;
    "median"/"trimmed" sort each coordinate with excluded slots pushed to the
    end behind a dtype-max sentinel and read the order statistics. Rows with
    zero contributing slots return sentinel-valued garbage — callers select
    those rows away (see ``robust_local_aggregate``).
    """
    cnt = jnp.sum(w, axis=1)  # [M]
    safe = jnp.maximum(cnt, 1.0)
    shape_m = lambda x: (-1,) + (1,) * (x.ndim - 2)
    wb = w.reshape(w.shape + (1,) * (x.ndim - 2)).astype(x.dtype)
    if method == "mean":
        return (jnp.sum(jnp.where(wb > 0, x, 0.0), axis=1)
                / safe.reshape(shape_m(x)).astype(x.dtype))
    big = jnp.asarray(jnp.finfo(x.dtype).max, x.dtype)
    s = jnp.sort(jnp.where(wb > 0, x, big), axis=1)
    if method == "median":
        cnt_i = cnt.astype(jnp.int32)
        lo = jnp.maximum((cnt_i - 1) // 2, 0).reshape((-1, 1) + shape_m(x)[1:])
        hi = jnp.maximum(cnt_i // 2, 0).reshape((-1, 1) + shape_m(x)[1:])
        take = lambda i: jnp.take_along_axis(
            s, jnp.broadcast_to(i, (x.shape[0], 1) + x.shape[2:]), axis=1)[:, 0]
        return 0.5 * (take(lo) + take(hi))
    if method != "trimmed":
        raise ValueError(f"unknown robust method {method!r}")
    t = jnp.minimum(jnp.floor(trim_frac * cnt), jnp.floor((cnt - 1.0) / 2.0))
    t = jnp.maximum(t, 0.0)  # cnt = 0 rows: keep the window empty-but-sane
    pos = jnp.arange(x.shape[1], dtype=jnp.float32).reshape(
        (1, -1) + (1,) * (x.ndim - 2))
    keep = ((pos >= t.reshape(shape_m(x))[:, None])
            & (pos < (cnt - t).reshape(shape_m(x))[:, None])).astype(x.dtype)
    denom = jnp.maximum(cnt - 2.0 * t, 1.0).reshape(shape_m(x)).astype(x.dtype)
    return jnp.sum(s * keep, axis=1) / denom


def robust_local_aggregate(theta2_active, pmask, trust, method: str = "median",
                           trim_frac: float = 0.1, agg_masks=None):
    """Eq. (1) under screening: [M, A, ...] -> [M, ...].

    ``pmask`` marks the round's real cohort slots, ``trust`` (same shape,
    1.0 = screening accepted every update this slot applied) the surviving
    ones. Per group:

      * screening passed (no real slot flagged) -> the EXACT
        ``local_aggregate(x, pmask)`` result, selected through ``jnp.where``
        — the fault-free path stays bit-identical to the masked mean;
      * flagged, with survivors -> the robust center over the surviving
        slots (masked mean / coordinate-wise median / trimmed mean);
      * flagged, no survivors -> the masked-mean fallback (the group is
        poisoned either way; its weight is zeroed upstream).

    ``agg_masks`` routes the clean-path mean through the secure-aggregation
    ring pipeline. The robust branch still reads the plaintext slots — a
    simulation privilege: coordinate-wise medians are nonlinear, so a real
    deployment cannot run them under vanilla pairwise masking and would pair
    screening with a different primitive.
    """
    w = pmask * trust
    flagged = jnp.sum(pmask * (1.0 - trust), axis=1)  # [M] flagged real slots
    cnt = jnp.sum(w, axis=1)
    use_robust = (flagged > 0) & (cnt > 0)
    if agg_masks is not None:
        plain = secure_local_aggregate(
            secure_mask_uplink(theta2_active, agg_masks), theta2_active, pmask)
    else:
        plain = local_aggregate(theta2_active, pmask)

    def robust_path(_):
        def sel(x_full, x_plain):
            # a conditional's branch takes no layout from its operand: pin it
            rob = _robust_center(lane_dense(x_full), w, method, trim_frac)
            keep = use_robust.reshape((-1,) + (1,) * (x_plain.ndim - 1))
            return jnp.where(keep, rob, x_plain)

        return jax.tree.map(sel, theta2_active, plain)

    # lax.cond, not jnp.where: an XLA conditional runs ONLY the taken branch,
    # so fault-free rounds never pay for the per-coordinate sorts (the
    # measured defense overhead budget is < 10% steps/s) — and the clean
    # branch returns the plain masked mean object itself, bit-identically
    out = jax.lax.cond(jnp.any(use_robust), robust_path, lambda _: plain, None)
    return _constrain_grouped(out)


def global_aggregate(theta, group_weights):
    """Eq. (2): weighted mean over groups. [M, ...] -> [...].

    With the group axis mesh-sharded this is a weighted reduce collective
    (psum of per-shard partial sums), not a replicated gather.
    """
    w = group_weights / jnp.sum(group_weights)

    def agg(x):
        wb = w.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
        return jnp.sum(x * wb, axis=0)

    return jax.tree.map(agg, theta)


def broadcast_to_groups(theta, M: int):
    """Send the global model back to every group. [...] -> [M, ...]."""
    return _constrain_grouped(
        jax.tree.map(lambda x: jnp.broadcast_to(x[None], (M,) + x.shape), theta))


def broadcast_to_devices(theta2_group, A: int):
    """Line 15: every sampled device restarts from the aggregated θ2_m."""
    return _constrain_grouped(jax.tree.map(
        lambda x: jnp.broadcast_to(x[:, None], (x.shape[0], A) + x.shape[1:]), theta2_group
    ))


def sample_participants(key, fed: FederationConfig) -> jnp.ndarray:
    """A_m + ξ_m: per-group device subset (== its samples). [M, A] indices."""
    M, K, A = fed.num_groups, fed.devices_per_group, fed.sampled_devices
    keys = jax.random.split(key, M)

    def pick(k):
        return jax.random.permutation(k, K)[:A]

    return jax.vmap(pick)(keys)


def gather_batch(data: Dict[str, jnp.ndarray], idx: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """data: {x1,x2,y,valid} with leading [M, K]; idx: [M, A] -> [M, A, ...]."""

    def take(x):
        return jax.vmap(lambda xi, ii: jnp.take(xi, ii, axis=0))(x, idx)

    return _constrain_grouped({k: take(v) for k, v in data.items()})
