"""Plain reference of one C-HSGD round of the paper's CNN (Algorithm 1,
eqs. (1)-(7); Fig. 10 for the model).

Model. Each 28x28 image is split by rows: the hospital holds the top 11
rows, the device the other 17. Each tower is conv3x3(1->16) + ReLU + 2x2
max-pool, conv3x3(16->32) + ReLU + 2x2 max-pool, flatten (row-major over
rows, columns, channels) and a bias-free linear map to 64. The combined
model takes [zeta1, zeta2] (128), a linear layer with bias to 128 + ReLU and
a linear layer with bias to the 11 classes; the loss is the mean softmax
cross entropy.

Round. Global aggregation (eq. 2: group-weighted mean, broadcast back),
then P/Q intervals of: local aggregation of the sampled device towers
(eq. 1), a fresh draw of A_m (A of the K devices of each group, one sample
each), the exchange of zeta1 = h1(theta1, X1), zeta2 = h2(theta2_m, X2) and a
snapshot of theta0, each compressed row by row (``compress.py``), and Q SGD
steps: hospitals step (theta0, theta1) on the mean loss of their A samples
with fresh zeta1 and the stale zeta2 (eqs. 5-6); each device steps its own
theta2 on its one sample with the stale theta0 and its stale zeta1 (eq. 7).

The draw of A_m uses the same jax.random calls on the same key as the
system under test (split the carried key into (next, draw); one key per
group; a permutation of the K devices, first A), so both train on the same
samples. Everything is computed in ``dtype``; float32 runs at `highest`
matmul precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import compress as C

ROWS, COLS, H_ROWS = 28, 28, 11


def conv_relu_pool(x, w, b):
    y = jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + b.astype(x.dtype)
    y = jax.nn.relu(y)
    return jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def tower(p, x_flat, rows):
    x = x_flat.reshape(x_flat.shape[0], rows, COLS, 1)
    x = conv_relu_pool(x, p["conv0"]["w"], p["conv0"]["b"])
    x = conv_relu_pool(x, p["conv1"]["w"], p["conv1"]["b"])
    return x.reshape(x.shape[0], -1) @ p["proj"]["w"].astype(x.dtype)


def combined(p, z1, z2):
    h = jnp.concatenate([z1, z2], axis=-1)
    h = jax.nn.relu(h @ p["fc1"]["w"] + p["fc1_b"])
    return h @ p["fc2"]["w"] + p["fc2_b"]


def xent(logits, y):
    logits = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def h1(p, x):
    return tower(p, x, H_ROWS)


def h2(p, x):
    return tower(p, x, ROWS - H_ROWS)


def _wmean(tree, w):
    """Group-weighted mean over the leading axis."""
    return jax.tree.map(lambda x: jnp.tensordot(w.astype(x.dtype), x, axes=1), tree)


def _bcast(tree, n):
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree)


def hospital_loss(t0, t1, x1, z2, y, keep):
    logits = combined(t0, h1(t1, x1), z2).astype(jnp.float32)
    picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    ce = jax.nn.logsumexp(logits, axis=-1) - picked
    return jnp.sum(ce * keep) / jnp.sum(keep)


def device_loss(t2, x2, y, t0, z1):
    return xent(combined(t0, z1[None], h2(t2, x2[None])), y[None])


def _round(weights, key, data, group_w, A: int, P: int, Q: int, k_frac: float,
           levels: int, lr: float, fault):
    M, K = data["y"].shape
    # the samples that count: all of them, or (a planted fault) the first half
    keep = (jnp.arange(A) < (A // 2 if fault == "half_batch" else A)).astype(jnp.float32)
    kept_mean = lambda x: jnp.tensordot(keep.astype(x.dtype), x, axes=([0], [1])) / jnp.sum(keep).astype(x.dtype)
    w = group_w / jnp.sum(group_w)
    # eq. (2) and its broadcast (all groups start equal: a no-op but for rounding)
    t0 = _bcast(_wmean(_bcast(weights["theta0"], M), w), M)
    t1 = _bcast(_wmean(_bcast(weights["theta1"], M), w), M)
    t2 = _bcast(_wmean(_bcast(weights["theta2"], M), w), M)  # per group
    t2 = jax.tree.map(lambda x: jnp.broadcast_to(x[:, None], (M, A) + x.shape[1:]), t2)
    losses, grad_norms = [], None
    for _ in range(P // Q):
        key, k_draw = jax.random.split(key)
        t2_group = jax.tree.map(kept_mean, t2)  # eq. (1)
        t2 = jax.tree.map(lambda x: jnp.broadcast_to(x[:, None], x.shape[:1] + (A,) + x.shape[1:]), t2_group)
        idx = jax.vmap(lambda k: jax.random.permutation(k, K)[:A])(jax.random.split(k_draw, M))
        take = jax.vmap(lambda a, i: a[i])
        x1, x2, y = (take(data[n], idx) for n in ("x1", "x2", "y"))
        x1, x2 = x1.astype(_dtype(t0)), x2.astype(_dtype(t0))
        z1 = jax.vmap(h1)(t1, x1)
        z2 = jax.vmap(h2)(t2_group, x2)
        stale = C.compress_tree({"theta0": t0, "z1": z1, "z2": z2}, k_frac, levels)
        for _ in range(Q):
            loss, (g0, g1) = jax.vmap(jax.value_and_grad(hospital_loss, argnums=(0, 1)),
                                      in_axes=(0, 0, 0, 0, 0, None))(
                t0, t1, x1, stale["z2"], y, keep)
            g2 = jax.vmap(jax.vmap(jax.grad(device_loss), in_axes=(0, 0, 0, None, 0)))(
                t2, x2, y, stale["theta0"], stale["z1"])
            g2 = jax.tree.map(lambda g: g * keep.reshape((1, A) + (1,) * (g.ndim - 2)).astype(g.dtype), g2)
            if grad_norms is None:
                g = {"theta0": _wmean(g0, w), "theta1": _wmean(g1, w),
                     "theta2": _wmean(jax.tree.map(kept_mean, g2), w)}
                grad_norms = jax.tree.map(lambda x: jnp.linalg.norm(x.astype(jnp.float32)), g)
            losses.append(jnp.mean(loss.astype(jnp.float32)))
            step = lambda p, g: p - jnp.asarray(lr, p.dtype) * g
            t0, t1, t2 = (jax.tree.map(step, t0, g0), jax.tree.map(step, t1, g1),
                          jax.tree.map(step, t2, g2))
    glob = {"theta0": _wmean(t0, w), "theta1": _wmean(t1, w),
            "theta2": _wmean(jax.tree.map(kept_mean, t2), w)}
    return jnp.stack(losses), glob, grad_norms


def _dtype(tree):
    return jax.tree_util.tree_leaves(tree)[0].dtype


_round_jit = jax.jit(_round, static_argnums=(4, 5, 6, 7, 8, 9, 10))


def round_readings(weights, key, data, group_w, A, P, Q, k_frac, levels, lr,
                   dtype=jnp.float32, fault=None):
    """(losses [P], per-leaf norm of the global model's change, per-leaf norm
    of the first step's global gradient) -- all float32 host values.
    ``fault="half_batch"`` plants a fault for the check's calibration: half
    of each group's samples are left out and means run over the rest."""
    w = jax.tree.map(lambda x: x.astype(dtype), weights)
    prec = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        losses, glob, gnorm = _round_jit(w, key, data, group_w, A, P, Q, k_frac,
                                         levels, lr, fault)
    change = jax.tree.map(
        lambda a, b: jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32)),
        glob, weights)
    return jax.device_get((losses, change, gnorm))
