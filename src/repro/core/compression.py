"""Message compression for the C-HSGD / C-TDCD baselines (paper §VII-A1).

Top-k sparsification (Compressed-VFL, Castiglia et al.) keeps the k largest-
magnitude entries of the exchanged tensor; the b-level quantization (paper:
b = 128 -> log2(b)/32 compression of surviving values) snaps values to a
uniform grid. Differentiable straight-through behaviour is NOT needed — the
paper compresses *messages*, not gradients, so we compress forward values.

This module is the canonical *math* for the compression pipeline. Two
implementations share it bit-for-bit:

  * ``compress_rows_ref`` — the pure-jnp fused reference (also the oracle for
    the Pallas kernel, re-exported by ``kernels/ref.py``). Ragged-aware: a
    per-row valid length lets many pytree leaves of different widths be
    compressed in ONE padded row-matrix call.
  * ``kernels/compress.py::fused_compress_pallas`` — the TPU kernel twin,
    which applies the same threshold refinement + quantization in a single
    VMEM-resident pass (one read, one write per message row).

Top-k uses the TPU-native *threshold refinement* formulation (fixed-iteration
binary search on the magnitude threshold against the row max) rather than a
sort: pure elementwise VPU work + row reductions, keeping >= k survivors
(exact top-k support always preserved; ties can add a few). The legacy
sort-based path is kept as ``topk_sparsify_sort`` for benchmarking the pre-
fusion hot path.
"""
from __future__ import annotations

import math
from typing import Optional, Union

import jax
import jax.numpy as jnp

N_REFINE = 16  # threshold tight to max|x| / 2^16


# ---------------------------------------------------------------------------
# Canonical fused math (fp32 internally; the kernel runs the same ops)
# ---------------------------------------------------------------------------


def compress_rows_ref(
    x: jnp.ndarray,
    k: Union[int, jnp.ndarray],
    levels: int = 0,
    row_len: Optional[jnp.ndarray] = None,
    dp_clip: Optional[jnp.ndarray] = None,
    dp_sigma: Optional[jnp.ndarray] = None,
    dp_noise: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Fused top-k sparsify + b-level quantize over the last axis of ``x``.

    x: [rows, n]. k: scalar or [rows]/[rows,1] per-row keep count (k >= n is a
    per-row no-op). levels <= 1 disables quantization. row_len: optional
    [rows]/[rows,1] int32 valid length for ragged rows — entries at column
    >= row_len are excluded from thresholds/extrema and zeroed in the output.

    Optional fused DP stage (``dp_noise is not None``): each row is L2-clipped
    to ``dp_clip`` then perturbed with ``dp_sigma * dp_clip * dp_noise`` BEFORE
    sparsification, so the released message is a post-processing of a Gaussian-
    mechanism output. ``dp_noise`` [rows, n] is precomputed standard-normal
    (threaded PRNG outside the kernel) so the Pallas twin and this fallback see
    identical operands and stay bit-identical; clip/σ are traced scalars. The
    stage is gated at the Python level: the non-DP trace is unchanged.

    This is the jnp fallback used off-TPU and the bit-exact oracle for the
    Pallas kernel (identical op sequence, all reductions in fp32).
    """
    k = jnp.asarray(k, jnp.int32).reshape(-1, 1) if not isinstance(k, int) else k
    xf = x.astype(jnp.float32)
    if row_len is None:
        valid = jnp.ones(x.shape, bool)
    else:
        row_len = jnp.asarray(row_len, jnp.int32).reshape(-1, 1)
        valid = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) < row_len
    if dp_noise is not None:
        # one extra VMEM-resident op on the row matrix: scale = min(1, C/‖x‖₂)
        # per row, then add σ·C·noise. With σ=0 and C >= ‖x‖₂ this multiplies
        # by exactly 1.0 and adds exactly 0.0 — bit-identical to the non-DP
        # pass (pinned by a property test).
        nrm2 = jnp.sum(jnp.where(valid, xf * xf, 0.0), axis=-1, keepdims=True)
        coef = jnp.minimum(1.0, dp_clip / jnp.maximum(jnp.sqrt(nrm2), 1e-12))
        xf = xf * coef + (dp_sigma * dp_clip) * dp_noise.astype(jnp.float32)
    mag = jnp.where(valid, jnp.abs(xf), 0.0)
    hi = jnp.max(mag, axis=-1, keepdims=True)
    lo = jnp.zeros_like(hi)

    def refine(_, carry):
        # invariant: count(lo) >= k > count(hi); converge on the largest
        # threshold still keeping >= k survivors (count >= k, NOT > k — the
        # strict form would settle one element low and keep k+1 per row)
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        count = jnp.sum(((mag >= mid) & valid).astype(jnp.int32), axis=-1, keepdims=True)
        return jnp.where(count >= k, mid, lo), jnp.where(count >= k, hi, mid)

    lo, hi = jax.lax.fori_loop(0, N_REFINE, refine, (lo, hi))
    kept = (mag >= lo) & valid  # >= k survivors (exactly k up to ties)
    y = jnp.where(kept, xf, 0.0)
    if levels and levels > 1:
        # Quantize over the SURVIVORS' value range and re-mask zeros after.
        # Taking extrema over all valid entries (the old grid) anchors qlo at
        # the row min of the sparsified row, so whenever a kept value is
        # negative the zeroed entries snap to round((0-qlo)/scale)*scale+qlo
        # != 0 and quantization silently re-densifies the message.
        qlo = jnp.min(jnp.where(kept, y, jnp.inf), axis=-1, keepdims=True)
        qhi = jnp.max(jnp.where(kept, y, -jnp.inf), axis=-1, keepdims=True)
        scale = jnp.maximum(qhi - qlo, 1e-12) / (levels - 1)
        y = jnp.where(kept, jnp.round((y - qlo) / scale) * scale + qlo, 0.0)
    return jnp.where(valid, y, 0.0).astype(x.dtype)


# ---------------------------------------------------------------------------
# Standalone primitives (property-test surface; same refinement math)
# ---------------------------------------------------------------------------


def topk_sparsify(x: jnp.ndarray, k_frac: float) -> jnp.ndarray:
    """Keep ~ceil(k_frac * n) largest-|x| entries of each row; zero the rest.

    Operates on the last axis via the threshold-refinement formulation (>= k
    survivors, exact top-k support preserved). k_frac >= 1 is a no-op.
    """
    if k_frac >= 1.0:
        return x
    n = x.shape[-1]
    k = max(1, int(round(k_frac * n)))
    return compress_rows_ref(x.reshape(-1, n), k, levels=0).reshape(x.shape)


def topk_sparsify_sort(x: jnp.ndarray, k_frac: float) -> jnp.ndarray:
    """Legacy sort-based exact top-k (jax.lax.top_k) — pre-fusion baseline."""
    if k_frac >= 1.0:
        return x
    n = x.shape[-1]
    k = max(1, int(round(k_frac * n)))
    mag = jnp.abs(x)
    thresh = jax.lax.top_k(mag, k)[0][..., -1:]
    return jnp.where(mag >= thresh, x, 0).astype(x.dtype)


def quantize(x: jnp.ndarray, levels: int) -> jnp.ndarray:
    """Uniform b-level quantize/dequantize per row (last axis).

    The grid is anchored at zero (points are integer multiples of the row's
    step), so already-sparsified rows stay sparse: 0 maps to exactly 0. The
    step is still the row's (max-min)/(levels-1), keeping the error bound at
    step/2; the zero-anchored grid can spend one extra code at a span edge,
    which the byte model ignores.
    """
    if levels <= 1:
        return x
    lo = jnp.min(x, axis=-1, keepdims=True)
    hi = jnp.max(x, axis=-1, keepdims=True)
    scale = jnp.maximum(hi - lo, 1e-12) / (levels - 1)
    q = jnp.round(x / scale)
    return (q * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# Message entry points
# ---------------------------------------------------------------------------


def compress_message(x: jnp.ndarray, k_frac: float, levels: int = 0) -> jnp.ndarray:
    """Compress one message tensor (any rank >= 1) along its last axis.

    Routes through the fused kernel path (Pallas on TPU, fused jnp fallback
    elsewhere) as a single [rows, n] call.
    """
    if not (0.0 < k_frac < 1.0) and not (levels and levels > 1):
        return x
    from repro.kernels.compress import compress_rows  # lazy: avoids import cycle

    n = x.shape[-1]
    k = n if not (0.0 < k_frac < 1.0) else max(1, int(round(k_frac * n)))
    return compress_rows(x.reshape(-1, n), k, levels).reshape(x.shape)


def compress_message_sort(x: jnp.ndarray, k_frac: float, levels: int = 0) -> jnp.ndarray:
    """Exact top-k by sorting, then a separate quantize.

    The witness for exact top-k (the fused threshold search keeps >= k
    entries a row) and the path of ``exchange(fused=False)``.
    """
    y = topk_sparsify_sort(x, k_frac) if 0.0 < k_frac < 1.0 else x
    if levels and levels > 1:
        y = quantize(y, levels)
    return y


# (k_frac, levels) rungs ordered loosest -> tightest wire size; rung 0 is the
# uncompressed message. The adaptive controller's byte governor walks DOWN
# this ladder (never up within a run) until the projected bytes fit the
# budget, so the compile-cache key set stays bounded by len(COMPRESSION_LADDER).
COMPRESSION_LADDER = (
    (0.0, 0),     # uncompressed
    (0.5, 128),   # top-50% + b=128 quantization
    (0.25, 128),  # the paper's C-HSGD operating point (§VII-A1)
    (0.1, 128),
    (0.05, 64),
)

# DP rung dimension alongside COMPRESSION_LADDER: σ multipliers the privacy
# governor walks UP (never down within a run) when the projected ε would bust
# the (ε, δ) budget. σ is a traced kernel operand, so unlike the compression
# rungs this ladder costs zero extra compiles.
DP_SIGMA_LADDER = (1.0, 2.0, 4.0, 8.0)


def compressed_bytes(n_elements: int, k_frac: float, levels: int, dense_bytes_per_el: int = 4) -> float:
    """Wire size of a compressed message.

    top-k: k values + k indices (32-bit); quantization: log2(b) bits/value.
    Matches the paper's 'compression ratio log2(b)/32' accounting. Pure-
    Python cost model — never traces.
    """
    k = n_elements if not (0.0 < k_frac < 1.0) else max(1, int(round(k_frac * n_elements)))
    bits_per_val = dense_bytes_per_el * 8
    if levels and levels > 1:
        bits_per_val = max(1, math.ceil(math.log2(levels)))
    value_bytes = k * bits_per_val / 8.0
    index_bytes = 0.0 if k == n_elements else k * 4.0
    return value_bytes + index_bytes
