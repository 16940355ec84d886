"""jit'd public wrappers around the Pallas kernels.

Backend selection is automatic: compiled Mosaic kernels on TPU, interpret
mode elsewhere (interpret executes the same kernel body for validation).
The fused compression op additionally short-circuits to its bit-identical
jnp reference off-TPU — interpret-mode Pallas is for validation, not the hot
path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.compress import compress_rows, default_interpret, fused_compress_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas


def topk_sparsify(x: jnp.ndarray, k_frac: float) -> jnp.ndarray:
    """Row-wise top-k sparsification of a message tensor (any rank >= 1)."""
    return fused_compress(x, k_frac, levels=0)


def fused_compress(x: jnp.ndarray, k_frac: float, levels: int = 0) -> jnp.ndarray:
    """Fused top-k + b-level quantize along the last axis (any rank >= 1)."""
    if k_frac >= 1.0 and not (levels and levels > 1):
        return x
    shape = x.shape
    n = shape[-1]
    k = n if k_frac >= 1.0 else max(1, int(round(k_frac * n)))
    return compress_rows(x.reshape(-1, n), k, levels).reshape(shape)


def flash_attention(q, k, v, scale=None, window=0):
    """q,k,v: [B, S, H, D] (kv heads already repeated to H). Causal.

    ``window`` may be a python int OR a traced int scalar (the per-layer
    window a stacked-layer scan threads through) — it rides into the kernel
    as an SMEM operand, so varying it never recompiles. Backend autodetect
    (compiled Mosaic on TPU, interpret elsewhere) happens in the kernel.
    """
    B, S, H, D = q.shape
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    out = flash_attention_pallas(qf, kf, vf, scale=scale, window=window)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def ssm_scan(a, b, h0):
    """Linear recurrence for [B, T, ...] a/b with state [B, ...]: any trailing
    dims are folded into channels."""
    B, T = a.shape[:2]
    trail = a.shape[2:]
    C = 1
    for d in trail:
        C *= d
    hs, h_last = ssm_scan_pallas(a.reshape(B, T, C), b.reshape(B, T, C), h0.reshape(B, C),
                                 interpret=default_interpret())
    return hs.reshape((B, T) + trail), h_last.reshape((B,) + trail)
