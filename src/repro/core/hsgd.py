"""Hybrid Stochastic Gradient Descent — the paper's Algorithm 1.

Training runs as a jitted 3-level loop mirroring the paper's timeline:

  scan over R global rounds                      (t mod P == 0 events)
    ├─ local agg (eq 1) + global agg (eq 2) + broadcasts (Alg. 1 lines 3–9)
    └─ scan over Λ = P/Q local intervals         (t mod Q == 0 events)
         ├─ local aggregation (eq 1, lines 10–12)
         ├─ A_m/ξ_m agreement + intermediate-result EXCHANGE (lines 13–21):
         │    ζ1 = h1(θ1; X1ξ), ζ2 = h2(θ2; X2ξ), stale θ0 snapshot
         │    (optionally top-k/quantize compressed — C-HSGD)
         └─ scan over Q SGD steps (lines 22–26):
              hospital: (θ0,θ1) step with FRESH ζ1, STALE ζ2   (eqs 5–6)
              devices:  θ2_n step with STALE θ0, STALE ζ1      (eq 7)

Only the sampled devices A_m are materialized ([M, A, ...]): unsampled
devices are reset to θ2_m at every local aggregation anyway (line 15), so
their state never influences the trajectory.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.config import FederationConfig, TrainConfig
from repro.common.pytree import tree_dot, tree_norm, tree_sub
from repro.core import federation as F
from repro.core.compression import compress_message_sort
from repro.models.split_model import HybridModel
from repro.optim import halving_schedule

# Algorithm 1's phases as named scopes of the compiled round. Every op a phase
# runs, its backward pass and XLA's fusions included, carries the scope's path
# in its HLO metadata (op_name), and so in a device trace, where the benchmark
# maps device time back to the phases. Scopes change metadata only.
PHASE_SCOPES = (
    "local_step/hospital",         # eqs. (5)-(6): the θ0/θ1 step
    "local_step/device",           # eq. (7): the per-device θ2 step
    "local_step/device/conv",      # its conv stack, devices on the lanes (cnn)
    "exchange",                    # lines 10-21: key split, fault and screen legs
    "exchange/local_aggregation",  # eq. (1) and the line-15 broadcast
    "exchange/sample",             # A_m/ξ_m draw and batch gather (line 13)
    "exchange/intermediate",       # ζ1 = h1, ζ2 = h2
    "exchange/compress",           # C-HSGD top-k + quantize (and DP)
    "global_aggregation",          # eq. (2) and broadcasts, lines 3-9
)


class HSGDState(NamedTuple):
    theta0: Any  # [M, ...] combined models
    theta1: Any  # [M, ...] hospital towers
    theta2: Any  # [M, A, ...] sampled-device towers
    stale: Dict[str, Any]  # {"theta0": [M,...], "z1": [M,A,...], "z2": [M,A,...]}
    batch: Dict[str, jnp.ndarray]  # gathered ξ_m: x1,x2,y,valid [M,A,...]
    key: jnp.ndarray
    step: jnp.ndarray


# ---------------------------------------------------------------------------
# Construction
# ---------------------------------------------------------------------------


def _placeholder_ctx(model: HybridModel, theta1, theta2, data, M: int, A: int):
    """Placeholder (batch, z1, z2) shaped for A device slots per group.

    Every run/round exchanges before the first SGD step, so the placeholders
    are overwritten unread — shape them with eval_shape (zero FLOPs) instead
    of running real forward passes.
    """
    idx = jnp.zeros((M, A), jnp.int32)
    batch = F.gather_batch(data, idx)
    z_shapes = jax.eval_shape(
        lambda t1, t2, b: (
            _h1_groups(model, t1, b["x1"]),
            _h2_groups(model, F.local_aggregate(t2), b["x2"]),
        ),
        theta1, theta2, batch,
    )
    z1, z2 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), z_shapes)
    return batch, z1, z2


def init_state(key, model: HybridModel, fed: FederationConfig, data, dtype=jnp.float32) -> HSGDState:
    """All groups start from the same global model (Alg. 1 line 1)."""
    k_init, k_run = jax.random.split(key)
    params = model.init(k_init, dtype)
    M, A = fed.num_groups, fed.sampled_devices
    theta0 = F.broadcast_to_groups(params["theta0"], M)
    theta1 = F.broadcast_to_groups(params["theta1"], M)
    theta2 = F.broadcast_to_devices(F.broadcast_to_groups(params["theta2"], M), A)
    batch, z1, z2 = _placeholder_ctx(model, theta1, theta2, data, M, A)
    # distinct buffers from theta0: donation in run() must not see aliases
    stale = {"theta0": jax.tree.map(jnp.copy, theta0), "z1": z1, "z2": z2}
    return HSGDState(theta0, theta1, theta2, stale, batch, k_run, jnp.zeros((), jnp.int32))


def resize_cohort(state: HSGDState, model: HybridModel, data, A_new: int) -> HSGDState:
    """Re-bucket the device-slot axis A between rounds ([M, A, ...] -> [M, A_new, ...]).

    Valid only at a round boundary, where every cohort round has already
    checked its device towers back in (θ2 slots uniform: the executor ends
    with θ2 ← broadcast(masked eq. (1))), so collapsing the slot axis by eq.
    (1) and re-broadcasting is exact. The stale/batch placeholders are
    re-shaped the same way ``init_state`` shapes them — the next round's
    first exchange overwrites them unread.
    """
    M, A = jax.tree_util.tree_leaves(state.theta2)[0].shape[:2]
    if A == A_new:
        return state
    theta2_group = F.local_aggregate(state.theta2)
    theta2 = F.broadcast_to_devices(theta2_group, A_new)
    batch, z1, z2 = _placeholder_ctx(model, state.theta1, theta2, data, M, A_new)
    stale = {"theta0": state.stale["theta0"], "z1": z1, "z2": z2}
    return state._replace(theta2=theta2, stale=stale, batch=batch)


# ---------------------------------------------------------------------------
# Forward helpers (vmapped over groups / devices)
# ---------------------------------------------------------------------------


def _h1_groups(model, theta1, x1):
    """[M,...]θ1 × [M,A,...]x1 -> ζ1 [M,A,...]."""
    return jax.vmap(model.h1)(theta1, x1)


def _h2_groups(model, theta2_group, x2):
    """[M,...]θ2_m × [M,A,...]x2 -> ζ2 [M,A,...] (device outputs from θ2_m)."""
    return jax.vmap(model.h2)(theta2_group, x2)


# ---------------------------------------------------------------------------
# The three gradient rules (eqs. (5)–(7))
# ---------------------------------------------------------------------------


def _hospital_loss(model, theta0_m, theta1_m, batch_m, stale_z2_m):
    """Group-level loss with fresh ζ1(θ1), stale ζ2 — drives eqs. (5)(6)."""
    z1 = model.h1(theta1_m, batch_m["x1"])
    return model.loss(theta0_m, z1, jax.lax.stop_gradient(stale_z2_m), batch_m["y"])


def _device_loss(model, theta2_n, x2_n, y_n, stale_theta0_m, stale_z1_n):
    """Per-device loss with stale θ0, stale ζ1, fresh ζ2(θ2_n) — eq. (7)."""
    z2 = model.h2(theta2_n, x2_n[None])
    return model.loss(
        jax.lax.stop_gradient(stale_theta0_m),
        jax.lax.stop_gradient(stale_z1_n[None]),
        z2,
        y_n[None],
    )


def _local_grads(model: HybridModel, state: HSGDState):
    """Per-worker gradients of lines 22–26: (losses [M], g0 [M,...], g1 [M,...],
    g2 [M,A,...]). Shared by the plain step and the probe-collecting step."""

    def h_loss(t0_m, t1_m, b_m, z2_m):
        return _hospital_loss(model, t0_m, t1_m, b_m, z2_m)

    with jax.named_scope("local_step/hospital"):
        h_grads = jax.vmap(jax.value_and_grad(h_loss, argnums=(0, 1)))(
            state.theta0, state.theta1, state.batch, state.stale["z2"]
        )
    (losses, (g0, g1)) = h_grads

    def d_loss(t2_n, x2_n, y_n, t0_m, z1_n):
        return _device_loss(model, t2_n, x2_n, y_n, t0_m, z1_n)

    # the model's own batched form where it has one, else grad of the batch-1
    # loss vmapped over devices within a group, then over groups
    device_grads = model.device_grads or jax.vmap(
        jax.vmap(jax.grad(d_loss), in_axes=(0, 0, 0, None, 0)))
    with jax.named_scope("local_step/device"):
        g2 = device_grads(
            state.theta2, state.batch["x2"], state.batch["y"], state.stale["theta0"],
            state.stale["z1"]
        )
    return losses, g0, g1, g2


def _apply_sgd(state: HSGDState, lr, g0, g1, g2) -> HSGDState:
    upd = lambda p, g: p - lr * g.astype(p.dtype)
    with jax.named_scope("local_step/hospital"):
        theta0 = jax.tree.map(upd, state.theta0, g0)
        theta1 = jax.tree.map(upd, state.theta1, g1)
    with jax.named_scope("local_step/device"):
        theta2 = jax.tree.map(lambda p, g: F.lane_dense(upd(p, g)), state.theta2, g2)
    return state._replace(theta0=theta0, theta1=theta1, theta2=theta2,
                          step=state.step + 1)


def local_sgd_step(model: HybridModel, state: HSGDState, lr) -> Tuple[HSGDState, jnp.ndarray]:
    """One iteration of lines 22–26 for every group and sampled device."""
    losses, g0, g1, g2 = _local_grads(model, state)
    return _apply_sgd(state, lr, g0, g1, g2), jnp.mean(losses)


def _worker_dev2(g, gbar, lead: int):
    """Σ_leaves ||g_worker − ḡ||² per worker: [M, ...]→[M] (lead=1) or
    [M, A, ...]→[M, A] (lead=2)."""
    per = jax.tree.map(
        lambda x, m: jnp.sum((x - m.reshape((1,) * lead + m.shape)) ** 2,
                             axis=tuple(range(lead, x.ndim))), g, gbar)
    return sum(jax.tree_util.tree_leaves(per))


def local_sgd_step_stats(
    model: HybridModel, state: HSGDState, lr, group_weights
) -> Tuple[HSGDState, jnp.ndarray, Dict[str, Any]]:
    """``local_sgd_step`` + the §VI-B online probe statistics, reusing the
    step's own gradients (no extra forward/backward passes):

      gbar    — the global-gradient proxy ∇F(θ̃): weighted group mean of
                (g0, g1) and of the device means of g2 (eqs. (1)/(2) applied
                to gradients instead of parameters);
      gnorm2  — ‖gbar‖² (strategy 3's ‖∇F‖² input);
      delta2  — mean squared deviation of per-worker gradients around gbar
                (Assumption 2's δ² estimator).
    """
    losses, g0, g1, g2 = _local_grads(model, state)
    gbar = {
        "theta0": F.global_aggregate(g0, group_weights),
        "theta1": F.global_aggregate(g1, group_weights),
        "theta2": F.global_aggregate(F.local_aggregate(g2), group_weights),
    }
    gnorm2 = tree_dot(gbar, gbar)
    delta2 = (
        jnp.mean(_worker_dev2(g0, gbar["theta0"], 1)
                 + _worker_dev2(g1, gbar["theta1"], 1))
        + jnp.mean(_worker_dev2(g2, gbar["theta2"], 2))
    )
    new_state = _apply_sgd(state, lr, g0, g1, g2)
    aux = {"gbar": gbar, "gnorm2": gnorm2, "delta2": delta2}
    return new_state, jnp.mean(losses), aux


# ---------------------------------------------------------------------------
# Fault injection + compiled screening (the fault-tolerant step)
# ---------------------------------------------------------------------------


def _inject_grads(g2, grad_fault):
    """Add the per-device fault term where nonzero: [M, A] -> every g2 leaf.

    Selected through jnp.where, NOT a blanket ``g + fault``: adding 0.0 would
    flip -0.0 gradients to +0.0 and break the fault-free bit-identity pin.
    NaN fault terms select the faulty branch (NaN != 0 is True). The whole
    injection sits behind a lax.cond: an XLA conditional leaves fault-free
    steps' gradient pipeline untouched at runtime (the per-leaf selects were
    a measurable fraction of the step on small models), and the identity
    branch returns g2 itself — bit-identical by construction.
    """

    def add(g2):
        def leaf(g):
            f = grad_fault.reshape(
                grad_fault.shape + (1,) * (g.ndim - 2)).astype(g.dtype)
            # pinned as in _apply_sgd: a conditional's branch takes no layout
            return F.lane_dense(jnp.where(f != 0, g + f, g))

        return jax.tree.map(leaf, g2)

    return jax.lax.cond(jnp.any(grad_fault != 0), add, lambda g: g, g2)


def local_sgd_step_guarded(
    model: HybridModel,
    state: HSGDState,
    lr,
    pmask: jnp.ndarray,
    grad_fault: Optional[jnp.ndarray] = None,
    screen: bool = False,
    zmax: float = 8.0,
) -> Tuple[HSGDState, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``local_sgd_step`` with optional fault injection and compiled screening.

    Screening is pure jnp.where masking — no host syncs, RP4-clean — and with
    every mask all-ones the applied update is bit-identical to the unguarded
    step. Per step it zeroes:

      * device updates whose g2 is non-finite, or whose gradient sq-norm
        exceeds ``zmax² ×`` the group's masked median device sq-norm
        (norm-outlier screen over the real, finite cohort slots);
      * group (θ0, θ1) updates whose hospital gradient is non-finite, or —
        with ≥ 3 groups — an outlier against the cross-group median norm.

    Returns (state, loss, dev_ok [M, A], grp_ok [M]); the reported loss
    averages only unflagged groups when any group is flagged.
    """
    losses, g0, g1, g2 = _local_grads(model, state)
    if grad_fault is not None:
        g2 = _inject_grads(g2, grad_fault)
    M = pmask.shape[0]
    if not screen:
        dev_ok = jnp.ones(pmask.shape, jnp.float32)
        grp_ok = jnp.ones((M,), jnp.float32)
        return _apply_sgd(state, lr, g0, g1, g2), jnp.mean(losses), dev_ok, grp_ok

    dn2 = F.worker_sqnorm(g2, lead=2)  # [M, A]
    finite_d = jnp.isfinite(dn2)
    med = F.masked_median_values(dn2, pmask * finite_d)  # [M]
    # Floor the screen scale with the fleet-wide median device norm: a ratio
    # cut against the per-group median alone falsely flags the one device
    # that still has signal once its peers converge (median -> ~0). The
    # floor only ever RAISES cuts, so NaN/Inf (isfinite) and scale faults
    # (x1e4 additive, x1e6 corruption — many orders above any fleet median)
    # are still caught.
    fleet = F.masked_median_values(
        dn2.reshape(1, -1), (pmask * finite_d).reshape(1, -1))[0]
    cut = (zmax * zmax) * jnp.maximum(jnp.maximum(med, fleet), 1e-30)
    dev_ok = (finite_d & (dn2 <= cut[:, None])).astype(jnp.float32)

    hn2 = F.worker_sqnorm(g0, lead=1) + F.worker_sqnorm(g1, lead=1)  # [M]
    grp_fin = jnp.isfinite(hn2)
    if M >= 3:  # the cross-group outlier cut needs a meaningful median
        gmed = F.masked_median_values(hn2[None, :], grp_fin[None, :].astype(jnp.float32))[0]
        # same converged-peer guard: floor with the fleet device-norm median
        gcut = (zmax * zmax) * jnp.maximum(jnp.maximum(gmed, fleet), 1e-30)
        grp_fin = grp_fin & (hn2 <= gcut)
    grp_ok = grp_fin.astype(jnp.float32)

    def mask_grp(g):
        ok = grp_ok.reshape((-1,) + (1,) * (g.ndim - 1))
        return jnp.where(ok > 0, g, jnp.zeros((), g.dtype))

    def mask_dev(g):
        ok = dev_ok.reshape(dev_ok.shape + (1,) * (g.ndim - 2))
        return jnp.where(ok > 0, g, jnp.zeros((), g.dtype))

    g0 = jax.tree.map(mask_grp, g0)
    g1 = jax.tree.map(mask_grp, g1)
    g2 = jax.tree.map(mask_dev, g2)

    n_ok = jnp.sum(grp_ok)
    loss_all = jnp.mean(losses)
    # where, not multiply: a flagged group's NaN loss would poison the sum
    loss_ok = jnp.sum(jnp.where(grp_ok > 0, losses, 0.0)) / jnp.maximum(n_ok, 1.0)
    loss = jnp.where(n_ok == M, loss_all, loss_ok)
    return _apply_sgd(state, lr, g0, g1, g2), loss, dev_ok, grp_ok


# ---------------------------------------------------------------------------
# Exchange + aggregations
# ---------------------------------------------------------------------------


def exchange(
    model: HybridModel,
    state: HSGDState,
    data,
    fed: FederationConfig,
    compression_k: float = 0.0,
    quant_levels: int = 0,
    fused: bool = True,
    idx: Optional[jnp.ndarray] = None,
    pmask: Optional[jnp.ndarray] = None,
    trust: Optional[jnp.ndarray] = None,
    msg_fault: Optional[jnp.ndarray] = None,
    screen: bool = False,
    dp_clip=None,
    dp_sigma=None,
    agg_masks=None,
) -> HSGDState:
    """Local aggregation (eq 1) + A_m/ξ_m agreement + ζ/θ0 exchange.

    With compression on, the whole exchange message (θ0 snapshot pytree + ζ1
    + ζ2) is compressed in ONE fused top-k+quantize row-matrix call (Pallas
    kernel on TPU, fused jnp elsewhere). ``fused=False`` takes the leaf-wise
    exact top-k by sorting instead.

    The cohort path (see ``core/population.py``) pins the round's participants
    by passing ``idx`` ([M, A] data-row indices, padded to the bucket size by
    repeating real members) and ``pmask`` ([M, A], 0 on padding slots): the
    per-interval A_m draw is skipped and eq. (1) excludes the padding slots.

    The fault-tolerant path adds three optional legs, all pure jnp.where
    selections so the clean case is bit-identical to the plain path:
    ``trust`` ([M, A], 1.0 = slot's updates passed screening) switches eq. (1)
    to ``robust_local_aggregate`` per ``fed.robust_agg``; ``msg_fault`` ([M],
    0 = clean) multiplies the group's compressed ζ2 uplink (bit-flip
    corruption); ``screen`` zeroes non-finite message entries at the receiver.

    Privacy legs (both gated at the Python level — the plain trace is
    unchanged): ``dp_clip``/``dp_sigma`` (traced scalars) run the message
    through the fused per-row clip + Gaussian-noise stage of the compression
    kernel, drawing the precomputed noise rows from a key split off the
    threaded state key; ``agg_masks`` (a per-round int32 pytree from
    ``F.secure_agg_masks``) routes eq. (1) through the pairwise-mask secure-
    aggregation ring, where the masks cancel exactly in the server sum.
    """
    with jax.named_scope("exchange"):
        dp = dp_clip is not None
        if dp:  # extra split only on the DP trace: the plain key stream is untouched
            key, k_sample, k_dp = jax.random.split(state.key, 3)
        else:
            key, k_sample = jax.random.split(state.key)
            k_dp = None
        with jax.named_scope("local_aggregation"):
            if trust is not None and pmask is not None:
                theta2_group = F.robust_local_aggregate(  # eq (1) under screening
                    state.theta2, pmask, trust,
                    method=fed.robust_agg, trim_frac=fed.trim_frac,
                    agg_masks=agg_masks)
            elif agg_masks is not None:
                theta2_group = F.secure_local_aggregate(  # eq (1) over masked uplinks
                    F.secure_mask_uplink(state.theta2, agg_masks), state.theta2, pmask)
            else:
                theta2_group = F.local_aggregate(state.theta2, pmask)  # eq (1)
            A = fed.sampled_devices if idx is None else idx.shape[1]
            theta2 = F.broadcast_to_devices(theta2_group, A)  # line 15

        with jax.named_scope("sample"):
            if idx is None:
                idx = F.sample_participants(k_sample, fed)  # line 13
            batch = F.gather_batch(data, idx)

        with jax.named_scope("intermediate"):
            z1 = _h1_groups(model, state.theta1, batch["x1"])
            z2 = _h2_groups(model, theta2_group, batch["x2"])
        stale_theta0 = state.theta0

        if compression_k or quant_levels or dp:
            with jax.named_scope("compress"):
                msg = {"theta0": stale_theta0, "z1": z1, "z2": z2}
                if fused:
                    from repro.kernels.compress import compress_pytree

                    msg = compress_pytree(msg, compression_k or 1.0, quant_levels,
                                          dp_clip=dp_clip, dp_sigma=dp_sigma,
                                          dp_key=k_dp)
                else:
                    if dp:
                        raise ValueError(
                            "DP is fused into the batched compression kernel; "
                            "the legacy sort path does not support dp_clip/dp_sigma")
                    comp = partial(compress_message_sort, k_frac=compression_k or 1.0,
                                   levels=quant_levels)
                    msg = jax.tree.map(comp, msg)
                stale_theta0, z1, z2 = msg["theta0"], msg["z1"], msg["z2"]

        if msg_fault is not None:  # corruption hits the compressed uplink payload
            def corrupt(z2):
                def leaf(x):
                    f = msg_fault.reshape(
                        (-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
                    return jnp.where(f != 0, x * f, x)

                return jax.tree.map(leaf, z2)

            # cond, not where: clean rounds skip the corruption kernels entirely
            z2 = jax.lax.cond(jnp.any(msg_fault != 0), corrupt, lambda z: z, z2)
        if screen:  # receiver-side screen: drop (zero) non-finite ζ2 entries.
            # Only the device uplink leg needs it: the fault model corrupts ζ2 in
            # flight, while θ0/ζ1 originate from hospital state that the per-step
            # group screen keeps finite — sweeping those (much larger) trees too
            # costs real step time for no detection.
            clean = lambda x: jnp.where(jnp.isfinite(x), x, jnp.zeros((), x.dtype))
            z2 = jax.tree.map(clean, z2)

        stale = {"theta0": stale_theta0, "z1": z1, "z2": z2}
        return state._replace(theta2=theta2, stale=stale, batch=batch, key=key)


def global_aggregation(state: HSGDState, fed: FederationConfig, group_weights) -> HSGDState:
    """Eq. (2) + broadcasts (Alg. 1 lines 3–9).

    The device-slot count is read off the state (not ``fed.sampled_devices``)
    so the cohort path, whose slot axis is the current bucket size, reuses
    this unchanged. Slots are uniform at round boundaries (check-in), so the
    unmasked eq. (1) here is exact.
    """
    M = fed.num_groups
    A = jax.tree_util.tree_leaves(state.theta2)[0].shape[1]
    with jax.named_scope("global_aggregation"):
        theta2_group = F.local_aggregate(state.theta2)
        g0 = F.global_aggregate(state.theta0, group_weights)
        g1 = F.global_aggregate(state.theta1, group_weights)
        g2 = F.global_aggregate(theta2_group, group_weights)
        return state._replace(
            theta0=F.broadcast_to_groups(g0, M),
            theta1=F.broadcast_to_groups(g1, M),
            theta2=F.broadcast_to_devices(F.broadcast_to_groups(g2, M), A),
        )


def global_model(state: HSGDState, group_weights) -> Dict[str, Any]:
    """The observable global model θ̃ (eq. (2))."""
    return {
        "theta0": F.global_aggregate(state.theta0, group_weights),
        "theta1": F.global_aggregate(state.theta1, group_weights),
        "theta2": F.global_aggregate(F.local_aggregate(state.theta2), group_weights),
    }


# ---------------------------------------------------------------------------
# Full jitted training run
# ---------------------------------------------------------------------------


def state_shardings(state: HSGDState, mesh: Mesh, rules=None) -> HSGDState:
    """NamedShardings for an HSGDState: the leading group axis M rides the
    mesh's horizontal ("data"/"pod") axes via the logical "group" rule; key
    and step stay replicated. Non-divisible leaves fall back to replication,
    so a trivial mesh degrades to the single-device layout."""
    from repro.common.sharding import group_sharding

    repl = NamedSharding(mesh, P())
    grouped = lambda tree: jax.tree.map(lambda x: group_sharding(x.shape, mesh, rules), tree)
    return HSGDState(
        theta0=grouped(state.theta0),
        theta1=grouped(state.theta1),
        theta2=grouped(state.theta2),
        stale=grouped(state.stale),
        batch=grouped(state.batch),
        key=repl,
        step=repl,
    )


def _global_grad_zeros(state: HSGDState):
    """Zero template shaped like the global-gradient proxy (one model copy)."""
    return {
        "theta0": jax.tree.map(lambda x: jnp.zeros(x.shape[1:], x.dtype), state.theta0),
        "theta1": jax.tree.map(lambda x: jnp.zeros(x.shape[1:], x.dtype), state.theta1),
        "theta2": jax.tree.map(lambda x: jnp.zeros(x.shape[2:], x.dtype), state.theta2),
    }


def place_on_mesh(state: HSGDState, data, group_weights, mesh: Optional[Mesh]):
    """Shard (state, data, weights) for a non-trivial mesh; no-op otherwise."""
    if mesh is None or mesh.devices.size <= 1:
        return state, data, group_weights
    from repro.common.sharding import group_sharding

    state = jax.device_put(state, state_shardings(state, mesh))
    data = jax.device_put(
        data, jax.tree.map(lambda x: group_sharding(x.shape, mesh), data))
    group_weights = jax.device_put(group_weights, NamedSharding(mesh, P()))
    return state, data, group_weights


@dataclass(frozen=True)
class HSGDRunner:
    """Compiled HSGD trainer for a (model, federation, train) configuration.

    ``run`` donates the state argument: the full replicated [M, A, ...] pytree
    is updated in place instead of double-buffered, so the caller's input
    state is consumed (rebind the return value, as every call site does).
    Passing a non-trivial ``mesh`` shards every leading group axis over the
    mesh's horizontal axes, lowering the eq. (1)/(2) aggregations and
    broadcasts to collectives instead of replicated gathers.

    The adaptive controller drives single rounds through ``round_fn``, which
    stages the scan lengths per (P, Q, compression) bucket: each bucket
    compiles once into a donating jitted executor and is cached, so a run
    whose intervals vary round-to-round pays one compile per distinct bucket
    instead of one per round. η stays a traced scalar — re-picking the
    learning rate never recompiles.
    """

    model: HybridModel
    fed: FederationConfig
    train: TrainConfig
    do_global_agg: bool = True  # False reproduces TDCD's missing phase
    fused_compression: bool = True  # False keeps the pre-fusion sort path
    # (P, Q, k, b, collect) bucket -> compiled round executor
    _round_cache: Dict = field(default_factory=dict, compare=False, repr=False)

    def _round_impl(self, state: HSGDState, data, group_weights,
                    lr: Union[Callable, jnp.ndarray, float],
                    Q: int, lam: int, compression_k: float, quant_levels: int,
                    collect: bool, idx=None, pmask=None,
                    dp_clip=None, dp_sigma=None, agg_masks=None):
        """One global round with staged scan lengths (Λ intervals × Q steps).

        ``lr`` is either a step->η schedule (fixed-interval ``run`` path) or a
        traced scalar (adaptive path). With ``collect`` the inner scan carries
        the previous step's global-gradient proxy and emits per-step probe
        stats; ρ secants pair consecutive steps *within* an interval only
        (same batch ⇒ a clean Lipschitz quotient), so Q = 1 rounds yield no ρ
        samples and the controller keeps its EMA.
        """
        fed, model = self.fed, self.model
        if self.do_global_agg:
            state = global_aggregation(state, fed, group_weights)
        lr_of = lr if callable(lr) else (lambda step: jnp.asarray(lr, jnp.float32))
        do_exchange = partial(
            exchange, model, data=data, fed=fed,
            compression_k=compression_k, quant_levels=quant_levels,
            fused=self.fused_compression, idx=idx, pmask=pmask,
            dp_clip=dp_clip, dp_sigma=dp_sigma, agg_masks=agg_masks,
        )

        if not collect:
            def interval(state, _):
                state = do_exchange(state)

                def sgd_step(state, _):
                    state, loss = local_sgd_step(model, state, lr_of(state.step))
                    return state, loss

                state, losses = jax.lax.scan(sgd_step, state, None, length=Q)
                return state, losses

            state, losses = jax.lax.scan(interval, state, None, length=lam)
            return state, losses.reshape(-1)

        zeros_g = _global_grad_zeros(state)

        def interval(state, _):
            state = do_exchange(state)

            def sgd_step(carry, _):
                state, prev_g, prev_ok = carry
                lr_t = lr_of(state.step)
                state, loss, aux = local_sgd_step_stats(model, state, lr_t, group_weights)
                diff = tree_norm(tree_sub(aux["gbar"], prev_g))
                den = lr_t * tree_norm(prev_g)
                rho = jnp.where(prev_ok > 0.5, diff / jnp.maximum(den, 1e-12), 0.0)
                stats = {"loss": loss, "gnorm2": aux["gnorm2"],
                         "delta2": aux["delta2"], "rho": rho, "rho_ok": prev_ok}
                return (state, aux["gbar"], jnp.ones((), jnp.float32)), stats

            (state, _, _), stats = jax.lax.scan(
                sgd_step, (state, zeros_g, jnp.zeros((), jnp.float32)), None, length=Q)
            return state, stats

        state, stats = jax.lax.scan(interval, state, None, length=lam)
        stats = jax.tree.map(lambda x: x.reshape(-1), stats)  # [Λ, Q] -> [P]
        return state, stats

    def _round(self, state: HSGDState, data, group_weights, lr_fn):
        return self._round_impl(
            state, data, group_weights, lr_fn,
            self.fed.local_interval, self.fed.lam,
            self.train.compression_k, self.train.quantization_bits,
            collect=False,
        )

    def round_fn(self, P: int, Q: int, compression_k: Optional[float] = None,
                 quant_levels: Optional[int] = None, collect_stats: bool = True,
                 dp: bool = False, secure_agg: bool = False):
        """Compiled single-round executor for a (P, Q, compression) bucket.

        fn(state, data, group_weights, lr) -> (state, stats) with stats a dict
        of [P] per-step arrays (loss/gnorm2/delta2/rho/rho_ok) when
        ``collect_stats``, else (state, losses [P]). Donates ``state`` like
        ``run``. Cached per bucket — the adaptive controller's round-varying
        (P, Q, k, b) settings compile once each.

        ``dp``/``secure_agg`` extend the cache key by exactly one enable bit
        each; the executor then takes extra TRACED operands — fn(state, data,
        group_weights, lr, dp_clip, dp_sigma[, agg_masks]) — so re-picking
        clip/σ per round (the controller's DP governor) or re-keying the
        pairwise masks per round never recompiles, à la traced-η.
        """
        if P < 1 or Q < 1 or P % Q:
            raise ValueError(f"P={P} must be a positive multiple of Q={Q}")
        k = self.train.compression_k if compression_k is None else compression_k
        b = self.train.quantization_bits if quant_levels is None else quant_levels
        key = (P, Q, k, b, collect_stats)
        if dp or secure_agg:
            key = key + (dp, secure_agg)
        fn = self._round_cache.get(key)
        if fn is None:
            lam = P // Q

            if dp or secure_agg:
                @partial(jax.jit, donate_argnums=(0,))
                def hsgd_private_round(state, data, group_weights, lr,
                                       dp_clip=None, dp_sigma=None,
                                       agg_masks=None):
                    return self._round_impl(
                        state, data, group_weights, lr, Q, lam, k, b,
                        collect_stats,
                        dp_clip=dp_clip if dp else None,
                        dp_sigma=dp_sigma if dp else None,
                        agg_masks=agg_masks if secure_agg else None)

                fn = self._round_cache[key] = hsgd_private_round
                return fn

            # named so compile_guard can attribute compiles per executor
            @partial(jax.jit, donate_argnums=(0,))
            def hsgd_round(state, data, group_weights, lr):
                return self._round_impl(state, data, group_weights, lr,
                                        Q, lam, k, b, collect_stats)

            fn = self._round_cache[key] = hsgd_round
        return fn

    def cohort_round_fn(self, P: int, Q: int, cohort_size: int,
                        compression_k: Optional[float] = None,
                        quant_levels: Optional[int] = None,
                        collect_stats: bool = True):
        """Compiled round executor over a sampled cohort of device slots.

        fn(state, data, group_weights, lr, participants, pmask) -> (state,
        stats|losses). ``participants`` [M, cohort_size] are the round's data
        rows (padded to the power-of-two bucket by repeating real members),
        ``pmask`` [M, cohort_size] is 1 on real slots; ``group_weights`` is a
        traced [M] vector, so the semi-async scheduler's staleness-damped
        effective weights never trigger a recompile. The state's device axis
        must already equal ``cohort_size`` (see ``resize_cohort``).

        The round ends with a check-in — θ2 ← broadcast(masked eq. (1)) — so
        device slots leave the round uniform: padding slots never leak into
        the next round and re-bucketing between rounds stays exact.

        Cached per (P, Q, cohort_size, k, b, collect) bucket: a population run
        whose cohort sizes vary round-to-round compiles one executor per
        bucket, not one per round.
        """
        if P < 1 or Q < 1 or P % Q:
            raise ValueError(f"P={P} must be a positive multiple of Q={Q}")
        if cohort_size < 1:
            raise ValueError(f"cohort_size={cohort_size} must be >= 1")
        k = self.train.compression_k if compression_k is None else compression_k
        b = self.train.quantization_bits if quant_levels is None else quant_levels
        key = (P, Q, cohort_size, k, b, collect_stats)
        fn = self._round_cache.get(key)
        if fn is None:
            lam = P // Q
            A = cohort_size

            @partial(jax.jit, donate_argnums=(0,))
            def hsgd_cohort_round(state, data, group_weights, lr, participants, pmask):
                state, out = self._round_impl(
                    state, data, group_weights, lr, Q, lam, k, b,
                    collect_stats, idx=participants, pmask=pmask)
                theta2_group = F.local_aggregate(state.theta2, pmask)
                state = state._replace(
                    theta2=F.broadcast_to_devices(theta2_group, A))
                return state, out

            fn = self._round_cache[key] = hsgd_cohort_round
        return fn

    def _guarded_round_impl(self, state, data, group_weights, lr, Q: int,
                            lam: int, k: float, b: int, idx, pmask,
                            grad_fault, msg_fault, screen: bool):
        """Cohort round with fault injection and (optionally) the compiled
        defense: per-step screening masks, receiver-side message screening,
        and the ``fed.robust_agg`` aggregation over surviving slots. With all
        fault terms zero and screening on, every mask stays all-ones and the
        parameter trajectory is bit-identical to ``_round_impl``'s cohort
        path (pinned by a test; the reported loss scalar may differ in the
        final ULP — XLA fuses the cross-group mean reduction differently in
        this graph)."""
        fed, model = self.fed, self.model
        if self.do_global_agg:
            state = global_aggregation(state, fed, group_weights)
        lr_of = lr if callable(lr) else (lambda step: jnp.asarray(lr, jnp.float32))
        do_exchange = partial(
            exchange, model, data=data, fed=fed,
            compression_k=k, quant_levels=b, fused=self.fused_compression,
            idx=idx, pmask=pmask, msg_fault=msg_fault, screen=screen,
        )

        def interval(carry, _):
            state, trust = carry
            state = do_exchange(state, trust=trust if screen else None)

            def sgd_step(carry, _):
                state, trust = carry
                state, loss, dev_ok, _grp_ok = local_sgd_step_guarded(
                    model, state, lr_of(state.step), pmask,
                    grad_fault=grad_fault, screen=screen, zmax=fed.screen_zmax)
                # sticky within the round: a flagged device stays out of
                # every later aggregation (x1.0 is bitwise identity: the
                # clean path's trust never changes)
                trust = trust * dev_ok
                return (state, trust), loss

            (state, trust), losses = jax.lax.scan(
                sgd_step, (state, trust), None, length=Q)
            return (state, trust), losses

        trust0 = jnp.ones_like(pmask)
        (state, trust), losses = jax.lax.scan(
            interval, (state, trust0), None, length=lam)
        # check-in: device slots leave the round uniform (robust under screen)
        A = pmask.shape[1]
        if screen:
            theta2_group = F.robust_local_aggregate(
                state.theta2, pmask, trust,
                method=fed.robust_agg, trim_frac=fed.trim_frac)
        else:
            theta2_group = F.local_aggregate(state.theta2, pmask)
        state = state._replace(theta2=F.broadcast_to_devices(theta2_group, A))
        flagged = jnp.sum(pmask * (1.0 - trust))
        return state, losses.reshape(-1), flagged

    def fault_round_fn(self, P: int, Q: int, cohort_size: int,
                       compression_k: Optional[float] = None,
                       quant_levels: Optional[int] = None,
                       robust: bool = True):
        """Compiled fault-injectable round executor (the resilient runtime's
        work-horse).

        fn(state, data, group_weights, lr, participants, pmask, grad_fault,
        msg_fault) -> (state, losses [P], flagged). ``grad_fault`` [M, A] and
        ``msg_fault`` [M] are traced values (0 = clean) — re-drawing faults
        each round never recompiles. ``robust=True`` folds the compiled
        defense in (screening masks + ``fed.robust_agg`` aggregation);
        ``robust=False`` is the naive stack: same injection, no defense.
        ``flagged`` counts real slot-updates the screen rejected (always 0.0
        on the naive path).

        Cached per (P, Q, cohort_size, k, b, robust) bucket alongside the
        plain executors — same one-executor-per-bucket discipline.
        """
        if P < 1 or Q < 1 or P % Q:
            raise ValueError(f"P={P} must be a positive multiple of Q={Q}")
        if cohort_size < 1:
            raise ValueError(f"cohort_size={cohort_size} must be >= 1")
        k = self.train.compression_k if compression_k is None else compression_k
        b = self.train.quantization_bits if quant_levels is None else quant_levels
        key = (P, Q, cohort_size, k, b, "robust" if robust else "faulty")
        fn = self._round_cache.get(key)
        if fn is None:
            lam = P // Q

            if robust:
                @partial(jax.jit, donate_argnums=(0,))
                def hsgd_robust_round(state, data, group_weights, lr,
                                      participants, pmask, grad_fault, msg_fault):
                    return self._guarded_round_impl(
                        state, data, group_weights, lr, Q, lam, k, b,
                        participants, pmask, grad_fault, msg_fault, screen=True)

                fn = hsgd_robust_round
            else:
                @partial(jax.jit, donate_argnums=(0,))
                def hsgd_faulty_round(state, data, group_weights, lr,
                                      participants, pmask, grad_fault, msg_fault):
                    return self._guarded_round_impl(
                        state, data, group_weights, lr, Q, lam, k, b,
                        participants, pmask, grad_fault, msg_fault, screen=False)

                fn = hsgd_faulty_round
            self._round_cache[key] = fn
        return fn

    def run(self, state: HSGDState, data, group_weights, rounds: int,
            mesh: Optional[Mesh] = None):
        """Execute ``rounds`` global rounds; returns (state, per-step losses).

        Donates ``state`` (no double-buffering of the [M, A, ...] pytree).
        """
        lr_fn = halving_schedule(self.train.learning_rate, self.train.lr_halve_every)
        state, data, group_weights = place_on_mesh(state, data, group_weights, mesh)

        @partial(jax.jit, donate_argnums=(0,))
        def go(state, data, group_weights):
            def body(state, _):
                return self._round(state, data, group_weights, lr_fn)

            return jax.lax.scan(body, state, None, length=rounds)

        if mesh is None or mesh.devices.size <= 1:
            state, losses = go(state, data, group_weights)
        else:
            # trace under the mesh: the federation's group-axis constraints
            # and the compress kernel's shard_map read it
            from repro.common.sharding import mesh_context

            with mesh_context(mesh):
                state, losses = go(state, data, group_weights)
        return state, losses.reshape(-1)

    def run_private(self, state: HSGDState, data, group_weights, rounds: int,
                    seed: int = 0, dp_clip: float = 0.0, dp_sigma: float = 0.0,
                    secure_agg: bool = False):
        """Fixed-interval run with the privacy legs on.

        A host round loop instead of ``run``'s scan: the secure-aggregation
        pairwise masks are host-generated (numpy, stream index 4) and re-keyed
        every round, which a traced scan cannot express. One executor compiles
        for the single (P, Q, k, b) bucket — clip/σ/masks are traced operands,
        so the loop never recompiles. η follows the halving schedule sampled
        at each round's first step (it is a per-round traced scalar here).

        Returns (state, per-step losses [rounds * P]).
        """
        dp = dp_clip > 0.0
        if dp_sigma > 0.0 and not dp:
            raise ValueError("dp_sigma > 0 requires a positive dp_clip")
        Q = self.fed.local_interval
        P = Q * self.fed.lam
        fn = self.round_fn(P, Q, collect_stats=False, dp=dp,
                           secure_agg=secure_agg)
        lr_fn = halving_schedule(self.train.learning_rate,
                                 self.train.lr_halve_every)
        losses, step = [], 0
        for r in range(rounds):
            kwargs = {}
            if dp:
                kwargs["dp_clip"] = jnp.asarray(dp_clip, jnp.float32)
                kwargs["dp_sigma"] = jnp.asarray(dp_sigma, jnp.float32)
            if secure_agg:
                kwargs["agg_masks"] = F.secure_agg_masks(state.theta2, seed, r)
            state, l = fn(state, data, group_weights, lr_fn(step), **kwargs)
            losses.append(l)
            step += P
        return state, jnp.concatenate([jnp.reshape(l, (-1,)) for l in losses])


def make_group_weights(data) -> jnp.ndarray:
    """K_m weights from the per-group valid-sample counts."""
    return jnp.sum(data["valid"].astype(jnp.float32), axis=1)


# checkpoint restores return a real HSGDState, not an anonymous namedtuple
from repro.checkpoint.ckpt import register_state_class as _register_state_class  # noqa: E402

_register_state_class(HSGDState)
