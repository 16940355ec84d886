"""The paper's CNN model (Fig. 10): hospital-side + device-side conv towers
(no FC) whose outputs (intermediate results ζ) feed a combined model.

Used for the OrganAMNIST reproduction: each 28x28 image is vertically split
by rows; the hospital holds the top ``h_rows`` rows (≈300 px), the device the
rest (≈484 px).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L


def conv_specs(k: int, c_in: int, c_out: int, name_scale=None) -> Dict[str, L.Spec]:
    return {
        "w": L.Spec((k, k, c_in, c_out), (None, None, None, None), "normal", name_scale),
        "b": L.Spec((c_out,), (None,), "zeros"),
    }


def conv2d(params, x, stride: int = 1):
    """SAME conv. Stride 1 uses an im2col + GEMM formulation: the HSGD hot
    path differentiates towers under vmap over groups/devices, and the
    batched-filter conv backward lowers to grouped convolutions that fall off
    XLA:CPU's fast path (and off the TPU MXU). Shifted-slice patches + a
    batched matmul keep both forward and backward on plain dot_general."""
    w = params["w"].astype(x.dtype)
    # even kernels pad asymmetrically under SAME ((k-1)//2, k//2) — the
    # symmetric im2col shift below only matches for odd k
    if stride != 1 or w.shape[0] % 2 == 0:
        y = jax.lax.conv_general_dilated(
            x, w,
            window_strides=(stride, stride),
            padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return y + params["b"].astype(x.dtype)
    k, _, c_in, c_out = w.shape
    B, H, W, _ = x.shape
    p = k // 2
    xp = jnp.pad(x, ((0, 0), (p, p), (p, p), (0, 0)))
    patches = jnp.concatenate(
        [xp[:, i:i + H, j:j + W, :] for i in range(k) for j in range(k)], axis=-1)
    y = patches @ w.reshape(k * k * c_in, c_out)
    return y + params["b"].astype(x.dtype)


def max_pool_2x2(x):
    """2x2/2 VALID max pool as crop + reshape + max.

    Bit-identical to ``lax.reduce_window`` (same window set: positions
    0,2,... up to the last full window) but its backward is a cheap masked
    add instead of the single-threaded SelectAndScatter op."""
    b, h, w, c = x.shape
    return x[:, : h // 2 * 2, : w // 2 * 2, :].reshape(
        b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def tower_specs(in_rows: int, width: int = 28, channels: Tuple[int, ...] = (16, 32), embed_dim: int = 64):
    s: Dict = {}
    c_prev = 1
    for i, c in enumerate(channels):
        s[f"conv{i}"] = conv_specs(3, c_prev, c)
        c_prev = c
    rows, cols = in_rows, width
    for _ in channels:
        rows, cols = max(1, rows // 2), max(1, cols // 2)
    s["proj"] = L.dense_specs(rows * cols * c_prev, embed_dim, (None, None))
    return s


def tower_forward(params, x_flat, in_rows: int, width: int = 28, n_conv: int = 2):
    """x_flat: [B, in_rows*width] pixel slice -> ζ [B, embed]."""
    B = x_flat.shape[0]
    x = x_flat.reshape(B, in_rows, width, 1)
    for i in range(n_conv):
        x = jax.nn.relu(conv2d(params[f"conv{i}"], x))
        x = max_pool_2x2(x)
    x = x.reshape(B, -1)
    return L.dense(params["proj"], x)


def conv2d_lanes(params, x):
    """SAME conv with a filter per sample and the sample axis last (on the
    lanes): x [H, W, Cin, N], params w [k, k, Cin, Cout, N], b [Cout, N] ->
    [H, W, Cout, N].

    With every sample holding its own filter there is no shared GEMM to feed
    the MXU, and under vmap over batch-1 samples ``conv2d``'s im2col, relu
    and pool run with 1- to 144-wide channel dims minor. Here each output is
    k·k·Cin multiply-adds over full-lane rows, and the shifted taps slice
    major dims only."""
    w, b = params["w"], params["b"]
    k, _, c_in, c_out, n = w.shape
    H, W = x.shape[:2]
    p = k // 2
    xp = jnp.pad(x, ((p, p), (p, p), (0, 0), (0, 0)))
    taps = jnp.stack([xp[i:i + H, j:j + W] for i in range(k) for j in range(k)], axis=2)
    w = w.reshape(k * k, c_in, c_out, n)
    return jnp.sum(taps[:, :, :, :, None] * w, axis=(2, 3)) + b


def max_pool_2x2_lanes(x):
    """``max_pool_2x2`` over [H, W, C, N] (the same windows and crop)."""
    h, w = x.shape[:2]
    return x[: h // 2 * 2, : w // 2 * 2].reshape(
        h // 2, 2, w // 2, 2, *x.shape[2:]).max(axis=(1, 3))


def tower_features_lanes(conv_params, x_flat, in_rows: int, width: int = 28):
    """``tower_forward``'s conv stack for a batch of towers that each see one
    sample, laid out with the tower axis on the lanes.

    conv_params: {conv<i>: {w [N, k, k, Cin, Cout], b [N, Cout]}} (one tower
    per row), x_flat [N, in_rows*width] -> the flattened pooled activation
    [N, rows*cols*C] in ``tower_forward``'s (row, col, channel) order."""
    n = x_flat.shape[0]
    lanes = lambda a: jnp.moveaxis(a, 0, -1)
    x = lanes(x_flat.reshape(n, in_rows, width))[:, :, None]  # [H, W, 1, N]
    for i in range(len(conv_params)):
        x = jax.nn.relu(conv2d_lanes(jax.tree.map(lanes, conv_params[f"conv{i}"]), x))
        x = max_pool_2x2_lanes(x)
    return jnp.moveaxis(x, -1, 0).reshape(n, -1)


def combined_specs(embed_dim: int, n_classes: int, hidden: int = 128):
    return {
        "fc1": L.dense_specs(2 * embed_dim, hidden, (None, None)),
        "fc1_b": L.Spec((hidden,), (None,), "zeros"),
        "fc2": L.dense_specs(hidden, n_classes, (None, None)),
        "fc2_b": L.Spec((n_classes,), (None,), "zeros"),
    }


def combined_forward(params, z1, z2):
    x = jnp.concatenate([z1, z2], axis=-1)
    x = jax.nn.relu(L.dense(params["fc1"], x) + params["fc1_b"].astype(x.dtype))
    return L.dense(params["fc2"], x) + params["fc2_b"].astype(x.dtype)


def classification_loss(logits, labels, weight_decay: float = 0.0, params=None):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    loss = -jnp.mean(ll)
    if weight_decay and params is not None:
        sq = sum(jnp.sum(jnp.square(p)) for p in jax.tree_util.tree_leaves(params))
        loss = loss + 0.5 * weight_decay * sq
    return loss
