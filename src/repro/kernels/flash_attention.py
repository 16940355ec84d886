"""Pallas TPU kernel: blocked (flash) causal attention with sliding window.

Grid = (batch*kv_heads*q_groups, num_q_blocks, num_kv_blocks); the kv axis is
the innermost ("arbitrary") dimension, so the online-softmax running state
(m, l, acc) persists in VMEM scratch across kv iterations and is flushed to
the output on the last one. Block shapes default to MXU-aligned (128, 128)
tiles with the full head_dim resident.

Sliding-window attention (gemma3 local layers, zamba2 shared block at
long_500k) masks per-element; fully-out-of-range blocks contribute zero via
the masked softmax, matching the pure-jnp oracle `ref.blockwise_attention`.
The window rides along as a (1, 1) int32 SMEM operand — NOT a static arg —
so the per-layer window array a `lax.scan` threads through the stacked
layers (a traced scalar) never forces a recompile per window value.

Backend selection: ``interpret=None`` auto-detects — compiled Mosaic on TPU,
interpret mode elsewhere, the same policy as the fused compression kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common.backend import default_interpret

NEG_INF = -2.0e38


def _flash_kernel(win_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_k: int, seq_len: int,
                  padded_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale  # [block_q, d]
    k = k_ref[0].astype(jnp.float32)  # [block_k, d]
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    # window <= 0 means full causal. Resolve it to a span on the scalar unit:
    # a span longer than the padded sequence admits every causal key. Mosaic
    # cannot lower a select whose operands are i1 vectors, so the mask is
    # built from int32 compares and `&` only.
    window = win_ref[0, 0]
    span = jnp.where(window > 0, window, padded_len + 1)
    ok = ((k_pos <= q_pos) & (k_pos > q_pos - span)
          & (k_pos < seq_len) & (q_pos < seq_len))
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]  # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ()))
    )
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_q", "block_k", "interpret")
)
def flash_attention_pallas(
    q: jnp.ndarray,  # [BH, S, D] (batch*heads flattened; kv already expanded to q heads)
    k: jnp.ndarray,  # [BH, S, D]
    v: jnp.ndarray,
    scale: float | None = None,
    window=0,  # python int OR traced int scalar; <=0 = full causal
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
):
    if interpret is None:
        interpret = default_interpret()
    BH, S, D = q.shape
    scale = scale if scale is not None else D ** -0.5
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    pad = (-S) % max(block_q, block_k)
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    Sp = q.shape[1]
    win_arr = jnp.asarray(window, jnp.int32).reshape(1, 1)
    grid = (BH, Sp // block_q, Sp // block_k)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
            seq_len=S, padded_len=Sp,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, i, j: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(win_arr, q, k, v)
    return out[:, :S]
