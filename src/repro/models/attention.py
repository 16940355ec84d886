"""Attention: GQA/MHA, MLA (DeepSeek latent), sliding-window, blockwise, KV cache.

Blockwise (online-softmax) attention is the pure-JAX twin of the Pallas flash
kernel (kernels/flash_attention.py) and is used whenever the score matrix
would not fit memory (long prefill); XLA-native einsum attention is used for
short sequences. Decode paths attend one query token against a cached K/V.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.common.sharding import constrain, use_weight
from repro.common.backend import default_interpret
from repro.models import layers as L
from repro.models.quant import dequantize_rows, is_int8, quantize_rows

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


def gqa_specs(cfg: ModelConfig) -> Dict[str, L.Spec]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    s = {
        "wq": L.Spec((d, cfg.num_heads, hd), ("embed", "heads", "head_dim")),
        "wk": L.Spec((d, cfg.num_kv_heads, hd), ("embed", "kv_heads", "head_dim")),
        "wv": L.Spec((d, cfg.num_kv_heads, hd), ("embed", "kv_heads", "head_dim")),
        "wo": L.Spec((cfg.num_heads, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        s["q_bias"] = L.Spec((cfg.num_heads, hd), ("heads", "head_dim"), "zeros")
        s["k_bias"] = L.Spec((cfg.num_kv_heads, hd), ("kv_heads", "head_dim"), "zeros")
        s["v_bias"] = L.Spec((cfg.num_kv_heads, hd), ("kv_heads", "head_dim"), "zeros")
    if cfg.qk_norm:
        s["q_norm"] = L.Spec((hd,), ("head_dim",), "ones")
        s["k_norm"] = L.Spec((hd,), ("head_dim",), "ones")
    return s


def mla_specs(cfg: ModelConfig) -> Dict[str, L.Spec]:
    """DeepSeek-V3 Multi-head Latent Attention."""
    d = cfg.d_model
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    qk_nope, qk_rope, vd = cfg.resolved_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq_a": L.Spec((d, qr), ("embed", None)),
        "q_a_norm": L.Spec((qr,), (None,), "ones"),
        "wq_b": L.Spec((qr, cfg.num_heads, qk_nope + qk_rope), (None, "heads", "head_dim")),
        "wkv_a": L.Spec((d, kvr + qk_rope), ("embed", None)),
        "kv_a_norm": L.Spec((kvr,), (None,), "ones"),
        "wkv_b": L.Spec((kvr, cfg.num_heads, qk_nope + vd), (None, "heads", "head_dim")),
        "wo": L.Spec((cfg.num_heads, vd, d), ("heads", "head_dim", "embed")),
    }


def attention_specs(cfg: ModelConfig) -> Dict[str, L.Spec]:
    return mla_specs(cfg) if cfg.attention == "mla" else gqa_specs(cfg)


# ---------------------------------------------------------------------------
# Masking
# ---------------------------------------------------------------------------


def _window_ok(q_pos_col, k_pos_row, window):
    """window may be a traced int scalar; <=0 means full causal attention."""
    window = jnp.asarray(window, jnp.int32)
    in_window = k_pos_row > (q_pos_col - window)
    return jnp.where(window > 0, in_window, True)


def causal_mask_bias(q_pos, k_pos, window=0):
    """Additive bias [..., Sq, Sk]; window>0 adds a sliding-window band."""
    ok = k_pos[..., None, :] <= q_pos[..., :, None]
    ok &= _window_ok(q_pos[..., :, None], k_pos[..., None, :], window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def window_from_flag(cfg: ModelConfig, is_global) -> jnp.ndarray:
    """Per-layer window scalar: 0 = full attention, else sliding window."""
    win = cfg.sliding_window or 0
    return jnp.where(is_global, jnp.int32(0), jnp.int32(win))


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------


def _sdpa(q, k, v, bias, scale):
    """q:[B,Sq,H,D] k,v:[B,Sk,KH,D] -> [B,Sq,H,D]; bias:[B?,Sq,Sk] additive."""
    B, Sq, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    qg = q.reshape(B, Sq, KH, G, D)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32)) * scale
    scores = scores + bias[:, None, None] if bias.ndim == 3 else scores + bias
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def _blockwise_sdpa(q, k, v, q_pos, k_pos, scale, window: int, kv_block: int = 1024):
    """Online-softmax attention, scanning over KV blocks (flash-style, pure JAX).

    Memory O(Sq * kv_block) instead of O(Sq * Sk).
    """
    B, Sq, H, D = q.shape
    Sk, KH = k.shape[1], k.shape[2]
    G = H // KH
    nblk = (Sk + kv_block - 1) // kv_block
    pad = nblk * kv_block - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=jnp.iinfo(jnp.int32).max)
    kb = k.reshape(B, nblk, kv_block, KH, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblk, kv_block, KH, D).transpose(1, 0, 2, 3, 4)
    pb = k_pos.reshape(B, nblk, kv_block).transpose(1, 0, 2)

    qg = (q * scale).reshape(B, Sq, KH, G, D).astype(jnp.float32)

    def step(carry, blk):
        m, l, acc = carry
        kc, vc, pc = blk
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kc.astype(jnp.float32))
        ok = pc[:, None, None, None, :] <= q_pos[:, None, None, :, None]
        ok &= _window_ok(q_pos[:, None, None, :, None], pc[:, None, None, None, :], window)
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bkgqs,bskd->bkgqd", p, vc.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KH, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KH, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KH, G, Sq, D), jnp.float32)
    # remat the kv-block body: backward recomputes the [.., Sq, kv_block]
    # score slab instead of saving an f32 stack per block (§Perf iteration 7;
    # the Pallas flash kernel does the same in-register on real TPUs)
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(step), (m0, l0, a0), (kb, vb, pb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA forward (train/prefill and decode)
# ---------------------------------------------------------------------------

BLOCKWISE_THRESHOLD = 2048  # use online-softmax above this Sk (memory roofline)


def _long_prefill_attention(q, k, v, positions, scale, window):
    """Attention for a long contiguous SERVING prefill block at position 0.

    Routed to the Pallas flash kernel when a compiled Mosaic backend is
    available (TPU — same ``default_interpret()`` autodetect the compression
    kernel uses); the pure-JAX online-softmax twin runs elsewhere, where
    interpret-mode Pallas would only add overhead. ``window`` may be a traced
    per-layer scalar — the kernel takes it as an SMEM operand.

    Inference-only (reached via ``fresh_cache``): the forward-only kernel has
    no VJP, so the TRAIN path (no kv_cache) must stay on the differentiable
    ``_blockwise_sdpa`` twin.
    """
    if not default_interpret():
        from repro.kernels.ops import flash_attention

        G = q.shape[2] // k.shape[2]
        kr = jnp.repeat(k, G, axis=2) if G > 1 else k
        vr = jnp.repeat(v, G, axis=2) if G > 1 else v
        return flash_attention(q, kr, vr, scale=scale, window=window)
    return _blockwise_sdpa(q, k, v, positions, positions, scale, window)


def _cache_write(cache, update, index):
    """Write ``update`` into ``cache`` at ``index`` along axis 1.

    A scalar index writes a contiguous [B, S, ...] span (multi-token prefill,
    one ``dynamic_update_slice`` per leaf); an int32 [B] vector writes S
    tokens per batch row starting at per-slot positions (continuous batching
    — freed decode slots sit at different offsets; S > 1 is the speculative
    verify block). Out-of-range vector indices are dropped, which lets the
    serving engine park inactive slots at ``cache_len`` instead of masking.
    """
    if jnp.ndim(index) == 1:
        b = jnp.arange(cache.shape[0])[:, None]
        cols = index[:, None] + jnp.arange(update.shape[1], dtype=index.dtype)
        return cache.at[b, cols].set(update.astype(cache.dtype), mode="drop")
    start = (0, index) + (0,) * (cache.ndim - 2)
    return jax.lax.dynamic_update_slice(cache, update.astype(cache.dtype), start)


def _write_kv_cache(kv_cache, k, v, positions, index):
    """Write (k, v, positions) into the cache; return it plus read views.

    A 3-tuple cache is full precision. A 5-tuple is the int8 layout
    ``(k_codes, v_codes, k_scale, v_scale, pos)``: the update rows are
    quantized per (batch, position, kv_head) row before the write, and the
    read views are dequantized copies — the persistent cache stays int8 (the
    memory win), the transient f32 view lives only inside the executor.
    """
    if len(kv_cache) == 5:
        ck, cv, cks, cvs, cpos = kv_cache
        kq, ksc = quantize_rows(k)
        vq, vsc = quantize_rows(v)
        ck, cks = _cache_write(ck, kq, index), _cache_write(cks, ksc, index)
        cv, cvs = _cache_write(cv, vq, index), _cache_write(cvs, vsc, index)
        cpos = _cache_write(cpos, positions, index)
        new_cache = (ck, cv, cks, cvs, cpos)
        return new_cache, dequantize_rows(ck, cks, k.dtype), dequantize_rows(cv, cvs, v.dtype), cpos
    ck, cv, cpos = kv_cache
    ck = _cache_write(ck, k, index)
    cv = _cache_write(cv, v, index)
    cpos = _cache_write(cpos, positions, index)
    return (ck, cv, cpos), ck, cv, cpos


def gqa_forward(
    params,
    x,
    positions,
    cfg: ModelConfig,
    window: int = 0,
    positions_3d=None,
    kv_cache: Optional[Tuple] = None,
    cache_index=None,
    fresh_cache: bool = False,
):
    """Returns (out, new_kv) — new_kv only when kv_cache is given (decode)."""
    hd = cfg.resolved_head_dim
    wq = use_weight(params["wq"], ("embed", "heads", "head_dim"))
    wk = use_weight(params["wk"], ("embed", "kv_heads", "head_dim"))
    wv = use_weight(params["wv"], ("embed", "kv_heads", "head_dim"))
    q = jnp.einsum("bsd,dhk->bshk", x, wq.astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, wk.astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, wv.astype(x.dtype))
    if cfg.qkv_bias:
        q = q + params["q_bias"].astype(x.dtype)
        k = k + params["k_bias"].astype(x.dtype)
        v = v + params["v_bias"].astype(x.dtype)
    if cfg.qk_norm:
        q = _head_rms(q, params["q_norm"])
        k = _head_rms(k, params["k_norm"])
    if cfg.mrope_sections:
        p3 = positions_3d if positions_3d is not None else L.text_positions_3d(positions)
        q = L.apply_mrope(q, p3, cfg.mrope_sections, cfg.rope_theta)
        k = L.apply_mrope(k, p3, cfg.mrope_sections, cfg.rope_theta)
    else:
        rot = int(hd * cfg.partial_rotary_factor)
        q = L.apply_partial_rope(q, positions, cfg.rope_theta, rot)
        k = L.apply_partial_rope(k, positions, cfg.rope_theta, rot)
    q = constrain(q, ("batch", "seq", "heads", None))
    scale = hd ** -0.5

    if kv_cache is not None:
        new_cache, ck, cv, cpos = _write_kv_cache(kv_cache, k, v, positions, cache_index)
        Sq, Sk = k.shape[1], ck.shape[1]
        if fresh_cache:
            # single-pass prefill into an empty cache: nothing precedes this
            # block, so attend within the freshly projected K/V — the cache
            # tail is all masked-out sentinels whose softmax terms are exact
            # zeros, so skipping it is bit-identical AND O(Sq²) not
            # O(Sq · cache_len). Long blocks go flash/online-softmax.
            # >=, not >: the engine prefills in power-of-two blocks, so a
            # 2048-token block is the longest that prompts of 2049-4095
            # tokens produce — with > they would never reach the flash path
            if Sq >= BLOCKWISE_THRESHOLD:
                out = _long_prefill_attention(q, k, v, positions, scale, window)
            else:
                bias = causal_mask_bias(positions, positions, window)
                out = _sdpa(q, k, v, bias, scale)
        elif Sq > 1 and Sq * Sk > BLOCKWISE_THRESHOLD ** 2:
            # later prefill blocks attend against earlier cache content too —
            # online-softmax over the cache keeps memory O(Sq * kv_block)
            # (sentinel positions mask the unwritten tail exactly)
            out = _blockwise_sdpa(q, ck, cv, positions, cpos, scale, window)
        else:
            bias = _decode_bias(positions, cpos, window)
            out = _sdpa(q, ck, cv, bias, scale)
    else:
        Sk = k.shape[1]
        if Sk > BLOCKWISE_THRESHOLD:
            # train path: must stay differentiable (jax.grad flows through)
            out = _blockwise_sdpa(q, k, v, positions, positions, scale, window)
        else:
            bias = causal_mask_bias(positions, positions, window)
            out = _sdpa(q, k, v, bias, scale)
        new_cache = None

    wo = use_weight(params["wo"], ("heads", "head_dim", "embed"))
    out = jnp.einsum("bshk,hkd->bsd", out, wo.astype(out.dtype))
    out = constrain(out, ("batch", "seq", "embed"))
    return out, new_cache


def _head_rms(x, scale, eps=1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(dt)


def _decode_bias(q_pos, k_pos, window):
    ok = k_pos[:, None, :] <= q_pos[:, :, None]
    ok &= _window_ok(q_pos[:, :, None], k_pos[:, None, :], window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# MLA forward — caches the compressed latent (DeepSeek-V3 style)
# ---------------------------------------------------------------------------


def mla_forward(
    params,
    x,
    positions,
    cfg: ModelConfig,
    window: int = 0,
    kv_cache: Optional[Tuple] = None,
    cache_index=None,
    fresh_cache: bool = False,
    **_,
):
    nope, rope_d, vd = cfg.resolved_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank

    wq_a = use_weight(params["wq_a"], ("embed", None))
    qa = jnp.einsum("bsd,dr->bsr", x, wq_a.astype(x.dtype))
    qa = L.rmsnorm({"scale": params["q_a_norm"]}, qa)
    wq_b = use_weight(params["wq_b"], (None, "heads", "head_dim"))
    q = jnp.einsum("bsr,rhk->bshk", qa, wq_b.astype(x.dtype))
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta)

    wkv_a = use_weight(params["wkv_a"], ("embed", None))
    kv_a = jnp.einsum("bsd,dr->bsr", x, wkv_a.astype(x.dtype))
    latent, k_rope_flat = kv_a[..., :kvr], kv_a[..., kvr:]
    latent = L.rmsnorm({"scale": params["kv_a_norm"]}, latent)
    k_rope = L.apply_rope(k_rope_flat[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]

    scale = (nope + rope_d) ** -0.5
    wkv_b = use_weight(params["wkv_b"], (None, "heads", "head_dim"))
    wo = use_weight(params["wo"], ("heads", "head_dim", "embed"))

    if kv_cache is not None:
        # ---- ABSORBED decode (§Perf iteration 1) --------------------------
        # Never expand the latent cache to per-head K/V: fold wkv_b's K-half
        # into the query and its V-half into the attention output, so the
        # per-step cost is O(B·H·S·r) instead of O(B·S·r·H·(d_n+d_v)).
        idx = cache_index
        if len(kv_cache) == 5:
            c_lat, c_rope, c_lat_s, c_rope_s, cpos = kv_cache
            lq, lsc = quantize_rows(latent)
            rq, rsc = quantize_rows(k_rope)
            c_lat, c_lat_s = _cache_write(c_lat, lq, idx), _cache_write(c_lat_s, lsc, idx)
            c_rope, c_rope_s = _cache_write(c_rope, rq, idx), _cache_write(c_rope_s, rsc, idx)
            cpos = _cache_write(cpos, positions, idx)
            new_cache = (c_lat, c_rope, c_lat_s, c_rope_s, cpos)
            c_lat = dequantize_rows(c_lat, c_lat_s, latent.dtype)
            c_rope = dequantize_rows(c_rope, c_rope_s, k_rope.dtype)
        else:
            c_lat, c_rope, cpos = kv_cache
            c_lat = _cache_write(c_lat, latent, idx)
            c_rope = _cache_write(c_rope, k_rope, idx)
            cpos = _cache_write(cpos, positions, idx)
            new_cache = (c_lat, c_rope, cpos)

        wk_abs = wkv_b[..., :nope]  # [r, H, nope]
        wv_abs = wkv_b[..., nope:]  # [r, H, vd]
        q_abs = jnp.einsum("bqhk,rhk->bqhr", q_nope, wk_abs.astype(x.dtype))
        # accumulate in f32 WITHOUT materializing an f32 copy of the cache
        s = jnp.einsum("bqhr,bsr->bhqs", q_abs, c_lat,
                       preferred_element_type=jnp.float32)
        s += jnp.einsum("bqhk,bsk->bhqs", q_rope, c_rope,
                        preferred_element_type=jnp.float32)
        s *= scale
        ok = cpos[:, None, None, :] <= positions[:, None, :, None]
        ok &= _window_ok(positions[:, None, :, None], cpos[:, None, None, :], window)
        s = jnp.where(ok, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out_lat = jnp.einsum("bhqs,bsr->bqhr", p.astype(c_lat.dtype), c_lat,
                             preferred_element_type=jnp.float32)
        out = jnp.einsum("bqhr,rhv->bqhv", out_lat, wv_abs.astype(jnp.float32)).astype(x.dtype)
        out = jnp.einsum("bshv,hvd->bsd", out, wo.astype(x.dtype))
        return constrain(out, ("batch", "seq", "embed")), new_cache

    # ---- prefill/train: expansion amortizes over the full sequence --------
    kv = jnp.einsum("bsr,rhk->bshk", latent, wkv_b.astype(x.dtype))
    k_nope, vv = kv[..., :nope], kv[..., nope:]
    s = jnp.einsum("bqhk,bshk->bhqs", q_nope.astype(jnp.float32), k_nope.astype(jnp.float32))
    s += jnp.einsum("bqhk,bsk->bhqs", q_rope.astype(jnp.float32), k_rope.astype(jnp.float32))
    s *= scale
    ok = positions[:, None, None, :] <= positions[:, None, :, None]
    ok &= _window_ok(positions[:, None, :, None], positions[:, None, None, :], window)
    s = jnp.where(ok, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqs,bshv->bqhv", p, vv.astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bshv,hvd->bsd", out, wo.astype(x.dtype))
    return constrain(out, ("batch", "seq", "embed")), None


def attention_forward(params, x, positions, cfg: ModelConfig, **kw):
    if cfg.attention == "mla":
        return mla_forward(params, x, positions, cfg, **kw)
    return gqa_forward(params, x, positions, cfg, **kw)


# ---------------------------------------------------------------------------
# KV cache construction
# ---------------------------------------------------------------------------


def make_kv_cache_specs(cfg: ModelConfig, batch: int, cache_len: int, dtype=jnp.bfloat16):
    """Per-layer cache ShapeDtypeStructs + logical axes for one layer.

    int8 caches carry two extra leaves per tuple — f32 per-row scales for the
    K and V codes — laid out ``(k, v, k_scale, v_scale, pos)`` so the int32
    position track stays the last leaf in both layouts.
    """
    quant = is_int8(dtype)
    if cfg.attention == "mla":
        kvr, rope_d = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        shapes = [
            jax.ShapeDtypeStruct((batch, cache_len, kvr), dtype),
            jax.ShapeDtypeStruct((batch, cache_len, rope_d), dtype),
        ]
        axes = [("batch", "cache_seq", None), ("batch", "cache_seq", None)]
        if quant:
            shapes += [jax.ShapeDtypeStruct((batch, cache_len), jnp.float32)] * 2
            axes += [("batch", "cache_seq")] * 2
    else:
        hd = cfg.resolved_head_dim
        shapes = [
            jax.ShapeDtypeStruct((batch, cache_len, cfg.num_kv_heads, hd), dtype),
            jax.ShapeDtypeStruct((batch, cache_len, cfg.num_kv_heads, hd), dtype),
        ]
        axes = [
            ("batch", "cache_seq", "kv_heads", None),
            ("batch", "cache_seq", "kv_heads", None),
        ]
        if quant:
            shapes += [jax.ShapeDtypeStruct(
                (batch, cache_len, cfg.num_kv_heads), jnp.float32)] * 2
            axes += [("batch", "cache_seq", "kv_heads")] * 2
    shapes.append(jax.ShapeDtypeStruct((batch, cache_len), jnp.int32))
    axes.append(("batch", "cache_seq"))
    return tuple(shapes), tuple(axes)
