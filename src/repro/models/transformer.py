"""Model assembly for all assigned families.

Layer parameters are stacked along a leading "stack" dimension and driven by
``jax.lax.scan`` (+ remat) so that 61–80-layer models lower to compact HLO —
essential for the 512-device dry-runs. Heterogeneous layer schedules
(gemma3's 5 local : 1 global attention, deepseek's first-k-dense, zamba2's
shared attention block) are expressed with per-layer metadata arrays or
super-block loops, never by unrolling all layers.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import ModelConfig
from repro.common.sharding import constrain
from repro.models import attention as A
from repro.models import layers as L
from repro.models import ssm as S
from repro.models.mlp import mlp_forward, mlp_specs
from repro.models.moe import moe_forward, moe_specs
from repro.models.quant import dequantize_rows, is_int8, quantize_rows


# ---------------------------------------------------------------------------
# Per-layer specs by family
# ---------------------------------------------------------------------------


def block_specs(cfg: ModelConfig, kind: str) -> Dict:
    """kind: attn_mlp | attn_moe | mamba | encdec."""
    d = cfg.d_model
    if kind == "mamba":
        return {
            "norm": L.norm_specs(cfg.norm, d),
            "mamba": S.mamba_specs(cfg),
        }
    if kind == "attn_moe":
        return {
            "norm1": L.norm_specs(cfg.norm, d),
            "attn": A.attention_specs(cfg),
            "norm2": L.norm_specs(cfg.norm, d),
            "moe": moe_specs(cfg),
        }
    return {
        "norm1": L.norm_specs(cfg.norm, d),
        "attn": A.attention_specs(cfg),
        "norm2": L.norm_specs(cfg.norm, d),
        "mlp": mlp_specs(cfg),
    }


def stack_specs(cfg: ModelConfig, n_layers: int, kind: str) -> Dict:
    """Stack per-layer specs along a leading layer dim."""
    one = block_specs(cfg, kind)
    return jax.tree.map(
        lambda s: L.Spec((n_layers,) + s.shape, ("stack",) + s.axes, s.init, s.scale),
        one,
        is_leaf=L.is_spec,
    )


# ---------------------------------------------------------------------------
# Block forwards
# ---------------------------------------------------------------------------


def attn_mlp_block(params, x, positions, cfg, window, kv_cache=None, cache_index=None,
                   positions_3d=None, fresh_cache=False):
    h = L.apply_norm(cfg.norm, params["norm1"], x)
    a, new_cache = A.attention_forward(
        params["attn"], h, positions, cfg, window=window,
        kv_cache=kv_cache, cache_index=cache_index, positions_3d=positions_3d,
        fresh_cache=fresh_cache,
    )
    x = x + a
    h = L.apply_norm(cfg.norm, params["norm2"], x)
    x = x + mlp_forward(params["mlp"], h, cfg)
    return x, new_cache


def attn_moe_block(params, x, positions, cfg, window, kv_cache=None, cache_index=None,
                   fresh_cache=False):
    h = L.apply_norm(cfg.norm, params["norm1"], x)
    a, new_cache = A.attention_forward(
        params["attn"], h, positions, cfg, window=window,
        kv_cache=kv_cache, cache_index=cache_index, fresh_cache=fresh_cache,
    )
    x = x + a
    h = L.apply_norm(cfg.norm, params["norm2"], x)
    m, aux = moe_forward(params["moe"], h, cfg)
    x = x + m
    return x, new_cache, aux


def mamba_block(params, x, cfg, state=None):
    h = L.apply_norm(cfg.norm, params["norm"], x)
    m, new_state = S.mamba_forward(params["mamba"], h, cfg, state)
    return x + m, new_state


# ---------------------------------------------------------------------------
# Stacked-scan drivers
# ---------------------------------------------------------------------------


def _remat(f, enabled: bool):
    return jax.checkpoint(f) if enabled else f


def dense_stack_forward(params, x, positions, cfg, windows, remat=True, positions_3d=None):
    """windows: int32 [L] per-layer sliding window (0 = full)."""

    def body(xc, layer):
        p, win = layer
        y, _ = attn_mlp_block(p, xc, positions, cfg, win, positions_3d=positions_3d)
        return y, None

    x, _ = jax.lax.scan(_remat(body, remat), x, (params, windows))
    return x


def moe_stack_forward(params, x, positions, cfg, windows, remat=True):
    def body(carry, layer):
        xc, aux = carry
        p, win = layer
        y, _, a = attn_moe_block(p, xc, positions, cfg, win)
        return (y, aux + a), None

    (x, aux), _ = jax.lax.scan(_remat(body, remat), (x, jnp.float32(0.0)), (params, windows))
    return x, aux


def mamba_stack_forward(params, x, cfg, remat=True):
    def body(xc, p):
        y, _ = mamba_block(p, xc, cfg)
        return y, None

    x, _ = jax.lax.scan(_remat(body, remat), x, params)
    return x


# decode variants: scan threads the per-layer cache --------------------------


def dense_stack_decode(params, x, positions, cfg, windows, caches, cache_index, fresh_cache=False):
    def body(xc, layer):
        p, win, cache = layer
        y, new_cache = attn_mlp_block(p, xc, positions, cfg, win, kv_cache=cache,
                                      cache_index=cache_index, fresh_cache=fresh_cache)
        return y, new_cache

    x, new_caches = jax.lax.scan(body, x, (params, windows, caches))
    return x, new_caches


def moe_stack_decode(params, x, positions, cfg, windows, caches, cache_index, fresh_cache=False):
    def body(xc, layer):
        p, win, cache = layer
        y, new_cache, _ = attn_moe_block(p, xc, positions, cfg, win, kv_cache=cache,
                                         cache_index=cache_index, fresh_cache=fresh_cache)
        return y, new_cache

    x, new_caches = jax.lax.scan(body, x, (params, windows, caches))
    return x, new_caches


def mamba_stack_decode(params, x, cfg, states):
    def body(xc, layer):
        p, st = layer
        y, new_st = mamba_block(p, xc, cfg, state=st)
        return y, new_st

    x, new_states = jax.lax.scan(body, x, (params, states))
    return x, new_states


# ---------------------------------------------------------------------------
# Layer schedules
# ---------------------------------------------------------------------------


def layer_windows(cfg: ModelConfig, n_layers: int, force_window: bool = False) -> jnp.ndarray:
    """Per-layer window array. gemma3: 5 local (sliding) : 1 global (full)."""
    win = cfg.sliding_window or 0
    if win == 0:
        return jnp.zeros((n_layers,), jnp.int32)
    if cfg.local_global_ratio > 0 and not force_window:
        period = cfg.local_global_ratio + 1
        flags = np.array(
            [0 if (i % period) == cfg.local_global_ratio else win for i in range(n_layers)],
            np.int32,
        )
        return jnp.asarray(flags)
    return jnp.full((n_layers,), win, jnp.int32)


# ---------------------------------------------------------------------------
# Full models
# ---------------------------------------------------------------------------


def model_specs(cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    s: Dict = {"embed": L.embed_specs(cfg.vocab_size, d)}
    if cfg.family in ("dense", "vlm"):
        s["layers"] = stack_specs(cfg, cfg.num_layers, "attn_mlp")
    elif cfg.family == "moe":
        nd = cfg.first_dense_layers
        if nd:
            s["dense_layers"] = stack_specs(cfg, nd, "attn_mlp")
        s["layers"] = stack_specs(cfg, cfg.num_layers - nd, "attn_moe")
    elif cfg.family == "ssm":
        s["layers"] = stack_specs(cfg, cfg.num_layers, "mamba")
    elif cfg.family == "hybrid":
        s["layers"] = stack_specs(cfg, cfg.num_layers, "mamba")
        s["shared_attn"] = block_specs(cfg, "attn_mlp")  # zamba2 shared block
    elif cfg.family == "audio":
        enc_cfg = cfg
        s["enc_layers"] = stack_specs(enc_cfg, cfg.encoder_layers, "attn_mlp")
        s["enc_norm"] = L.norm_specs(cfg.norm, d)
        s["layers"] = stack_specs(cfg, cfg.num_layers, "attn_mlp")  # decoder self-attn
        s["cross_layers"] = stack_specs(cfg, cfg.num_layers, "attn_mlp")  # cross-attn + mlp reuse
    else:
        raise ValueError(cfg.family)
    s["final_norm"] = L.norm_specs(cfg.norm, d)
    if not cfg.tie_embeddings:
        s["head"] = L.dense_specs(d, cfg.vocab_size, (None, "vocab"), scale=0.02)
    return s


def embed_tokens(cfg, params, tokens):
    """Token embeddings, scaled by sqrt(d_model) unless ``cfg.embed_scale``
    is off (stablelm-2 feeds them to the first layer as they are)."""
    x = L.embed(params["embed"], tokens)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    return x


def _vlm_inputs(cfg, params, tokens, vision_embeds):
    """qwen2-vl: prepend stubbed patch embeddings to the token embeddings."""
    x_txt = L.embed(params["embed"], tokens) * jnp.sqrt(jnp.float32(cfg.d_model)).astype(jnp.bfloat16)
    if vision_embeds is None:
        return x_txt, None
    B, P, _ = vision_embeds.shape
    x = jnp.concatenate([vision_embeds.astype(x_txt.dtype), x_txt], axis=1)
    # M-RoPE 3D positions: vision patches get (t=0, h, w) grid; text continues 1D
    side = max(1, int(np.sqrt(P)))
    hh = (jnp.arange(P) // side).astype(jnp.int32)
    ww = (jnp.arange(P) % side).astype(jnp.int32)
    p_vis = jnp.stack([jnp.zeros((P,), jnp.int32), hh, ww], axis=-1)
    t_txt = jnp.arange(tokens.shape[1], dtype=jnp.int32) + jnp.max(hh) + 1
    p_txt = jnp.stack([t_txt, t_txt, t_txt], axis=-1)
    p3 = jnp.concatenate([p_vis, p_txt], axis=0)[None].repeat(B, 0)
    return x, p3


def forward(
    cfg: ModelConfig,
    params,
    tokens,
    *,
    extra_embeds=None,
    remat: bool = True,
    force_window: bool = False,
):
    """Training/prefill forward -> (hidden [B,S,D], aux_loss)."""
    aux = jnp.float32(0.0)
    positions_3d = None
    if cfg.family == "vlm":
        x, positions_3d = _vlm_inputs(cfg, params, tokens, extra_embeds)
    elif cfg.family == "audio":
        x = L.embed(params["embed"], tokens)
    else:
        x = embed_tokens(cfg, params, tokens)
    x = constrain(x, ("batch", "seq", "embed"))
    if cfg.family == "audio":
        x = audio_forward(params, x, extra_embeds, None, cfg, remat)
    else:
        x, aux = backbone_forward(cfg, params, x, remat=remat, force_window=force_window, positions_3d=positions_3d)
    x = L.apply_norm(cfg.norm, params["final_norm"], x)
    return x, aux


def backbone_forward(cfg: ModelConfig, params, x, *, remat=True, force_window=False, positions_3d=None):
    """Run the layer stacks over already-embedded inputs x [B, S, D]."""
    aux = jnp.float32(0.0)
    B, Stot = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(Stot, dtype=jnp.int32), (B, Stot))
    windows = layer_windows(cfg, cfg.num_layers, force_window)

    if cfg.family in ("dense", "vlm"):
        x = dense_stack_forward(params["layers"], x, positions, cfg, windows, remat, positions_3d)
    elif cfg.family == "moe":
        nd = cfg.first_dense_layers
        if nd:
            x = dense_stack_forward(params["dense_layers"], x, positions, cfg, windows[:nd], remat)
        x, aux = moe_stack_forward(params["layers"], x, positions, cfg, windows[nd:], remat)
    elif cfg.family == "ssm":
        x = mamba_stack_forward(params["layers"], x, cfg, remat)
    elif cfg.family == "hybrid":
        x = hybrid_forward(params, x, positions, cfg, windows, remat, force_window)
    else:
        raise ValueError(cfg.family)
    return x, aux


def hybrid_forward(params, x, positions, cfg, windows, remat=True, force_window=False):
    """zamba2: mamba super-blocks with one SHARED attention block between them."""
    period = cfg.hybrid_attn_every or cfg.num_layers
    n_sb = cfg.num_layers // period
    win = jnp.int32(cfg.sliding_window if (cfg.sliding_window and force_window) else 0)
    shared = params["shared_attn"]

    def run_sb(xc, sb_params):
        def body(h, p):
            y, _ = mamba_block(p, h, cfg)
            return y, None

        xc, _ = jax.lax.scan(_remat(body, remat), xc, sb_params)
        return xc

    for i in range(n_sb):
        sb = jax.tree.map(lambda a: jax.lax.slice_in_dim(a, i * period, (i + 1) * period, axis=0), params["layers"])
        x = run_sb(x, sb)
        y, _ = attn_mlp_block(shared, x, positions, cfg, win)
        x = y
    rem = cfg.num_layers - n_sb * period
    if rem:
        sb = jax.tree.map(lambda a: jax.lax.slice_in_dim(a, n_sb * period, cfg.num_layers, axis=0), params["layers"])
        x = run_sb(x, sb)
    return x


def encode_audio(cfg, params, enc_embeds, remat=False):
    """whisper encoder over stubbed frame embeddings -> [B, Se, D].

    The ONE encoder entry point: the training forward (``audio_forward``) and
    the serving engine's prefill both run it. For serving, the encoder output
    is immediately projected to per-layer cross-attention K/V
    (``seed_audio_caches``) and carried in the decode caches as ``cross`` —
    decode never re-touches the encoder output itself.
    """
    B, Se = enc_embeds.shape[0], enc_embeds.shape[1]
    enc_pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))
    zero_w = jnp.zeros((cfg.encoder_layers,), jnp.int32)

    def enc_body(h, layer):
        p, _ = layer
        hn = L.apply_norm(cfg.norm, p["norm1"], h)
        # bidirectional self-attention == unmasked cross-attention with itself
        a = cross_attention(p["attn"], hn, hn, enc_pos, enc_pos, cfg)
        h = h + a
        hn = L.apply_norm(cfg.norm, p["norm2"], h)
        h = h + mlp_forward(p["mlp"], hn, cfg)
        return h, None

    enc, _ = jax.lax.scan(_remat(enc_body, remat), enc_embeds, (params["enc_layers"], zero_w))
    return L.apply_norm(cfg.norm, params["enc_norm"], enc)


def audio_forward(params, dec_tokens_embedded, enc_embeds, positions, cfg, remat=True):
    """whisper: encoder over stubbed frames, decoder w/ interleaved cross-attn."""
    B = dec_tokens_embedded.shape[0]
    Se = enc_embeds.shape[1]
    enc_pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))
    enc = encode_audio(cfg, params, enc_embeds.astype(dec_tokens_embedded.dtype), remat)

    x = dec_tokens_embedded
    Sd = x.shape[1]
    dpos = jnp.broadcast_to(jnp.arange(Sd, dtype=jnp.int32), (B, Sd))

    def dec_body(h, layer):
        p_self, p_cross = layer
        hn = L.apply_norm(cfg.norm, p_self["norm1"], h)
        a, _ = A.attention_forward(p_self["attn"], hn, dpos, cfg, window=0)
        h = h + a
        hn = L.apply_norm(cfg.norm, p_self["norm2"], h)
        h = h + mlp_forward(p_self["mlp"], hn, cfg)
        # cross-attention: queries from decoder, kv from encoder
        hn = L.apply_norm(cfg.norm, p_cross["norm1"], h)
        c = cross_attention(p_cross["attn"], hn, enc, dpos, enc_pos, cfg)
        h = h + c
        hn = L.apply_norm(cfg.norm, p_cross["norm2"], h)
        h = h + mlp_forward(p_cross["mlp"], hn, cfg)
        return h, None

    x, _ = jax.lax.scan(_remat(dec_body, remat), x, (params["layers"], params["cross_layers"]))
    return x


def cross_attention(params, xq, xkv, q_pos, k_pos, cfg):
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", xq, params["wq"].astype(xq.dtype))
    k = jnp.einsum("bsd,dhk->bshk", xkv, params["wk"].astype(xq.dtype))
    v = jnp.einsum("bsd,dhk->bshk", xkv, params["wv"].astype(xq.dtype))
    bias = jnp.zeros((q_pos.shape[0], q_pos.shape[1], k_pos.shape[1]), jnp.float32)
    out = A._sdpa(q, k, v, bias, hd ** -0.5)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(xq.dtype))


def cross_attention_cached(params, xq, k, v, cfg):
    """Cross-attention against PRE-PROJECTED encoder K/V ([B, Se, KH, hd]).

    The decode-path twin of ``cross_attention``: only the query projection
    runs per step — the K/V einsums that used to dominate whisper decode
    (satellite bugfix: the 1.2× decode ratio) happen once at prefill.
    """
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", xq, params["wq"].astype(xq.dtype))
    bias = jnp.zeros((xq.shape[0], xq.shape[1], k.shape[1]), jnp.float32)
    out = A._sdpa(q, k.astype(xq.dtype), v.astype(xq.dtype), bias, hd ** -0.5)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(xq.dtype))


def audio_cross_kv(cfg, params, enc):
    """Project encoder output to stacked per-layer cross K/V.

    enc [B, Se, D] -> (k, v) each [L, B, Se, KH, hd]: one einsum over the
    layer-stacked weights instead of L per-step projections.
    """
    wk = params["cross_layers"]["attn"]["wk"]
    wv = params["cross_layers"]["attn"]["wv"]
    k = jnp.einsum("bsd,ldhk->lbshk", enc, wk.astype(enc.dtype))
    v = jnp.einsum("bsd,ldhk->lbshk", enc, wv.astype(enc.dtype))
    return k, v


def seed_audio_caches(cfg, params, caches, enc_embeds):
    """Run the encoder and fill the read-only ``cross`` K/V cache leaves.

    Serving prefill entry point: quantizes per row when the cache layout is
    int8 (4 leaves), otherwise casts to the cache dtype.
    """
    enc = encode_audio(cfg, params, enc_embeds)
    k, v = audio_cross_kv(cfg, params, enc)
    cross = caches["cross"]
    if len(cross) == 4:
        kq, ks = quantize_rows(k)
        vq, vs = quantize_rows(v)
        new_cross = (kq, vq, ks, vs)
    else:
        new_cross = (k.astype(cross[0].dtype), v.astype(cross[1].dtype))
    return {**caches, "cross": new_cross}


def logits_from_hidden(cfg: ModelConfig, params, hidden):
    if cfg.tie_embeddings:
        out = L.unembed(params["embed"], hidden)
    else:
        out = L.dense(params["head"], hidden)
    return constrain(out, ("batch", "seq", "vocab"))


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def cross_entropy(logits, labels, z_loss: float = 0.0):
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = jnp.mean(lse - ll)
    if z_loss:
        loss = loss + z_loss * jnp.mean(jnp.square(lse))
    return loss


CE_CHUNK = 512


def chunked_lm_head_loss(cfg: ModelConfig, params, hidden, labels, remat=True):
    """Fused head-matmul + cross-entropy over sequence chunks (§Perf it. 6).

    The full [B, S, V] logits tensor (and its fp32 copies inside logsumexp)
    never materializes: each scan step computes a [B, CE_CHUNK, V] slab,
    reduces it to a scalar, and is rematerialized in the backward pass.
    """
    B, S, D = hidden.shape
    chunk = min(CE_CHUNK, S)
    pad = (-S) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nch = hidden.shape[1] // chunk
    h_c = jnp.moveaxis(hidden.reshape(B, nch, chunk, D), 1, 0)
    y_c = jnp.moveaxis(labels.reshape(B, nch, chunk), 1, 0)

    def body(acc, xs):
        hc, yc = xs
        logits = logits_from_hidden(cfg, params, hc).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, jnp.maximum(yc, 0)[..., None], axis=-1)[..., 0]
        valid = (yc >= 0).astype(jnp.float32)
        return acc + jnp.sum((lse - ll) * valid), None

    body = _remat(body, remat)
    total, _ = jax.lax.scan(body, jnp.float32(0.0), (h_c, y_c))
    return total / (B * S)


def lm_loss(cfg: ModelConfig, params, batch, remat=True, aux_weight=0.01, force_window=False):
    hidden, aux = forward(
        cfg, params, batch["tokens"], extra_embeds=batch.get("extra_embeds"),
        remat=remat, force_window=force_window,
    )
    if cfg.family == "vlm" and batch.get("extra_embeds") is not None:
        hidden = hidden[:, batch["extra_embeds"].shape[1] :]
    return chunked_lm_head_loss(cfg, params, hidden, batch["labels"], remat) + aux_weight * aux


# ---------------------------------------------------------------------------
# Decode (serve_step core)
# ---------------------------------------------------------------------------


def make_decode_caches(cfg: ModelConfig, batch: int, cache_len: int, dtype=jnp.bfloat16):
    """ShapeDtypeStructs for stacked per-layer caches + logical axes trees.

    ``dtype=int8`` selects the quantized cache layouts (extra f32 scale
    leaves; see models/quant.py). SSM states stay f32 for every non-int8
    dtype — the recurrence is precision-sensitive — but adopt the quantized
    layout under int8 so the whole cache tree shrinks together.
    """
    # SSM recurrences carry f32 state unless explicitly quantized to int8
    sdtype = dtype if is_int8(dtype) else jnp.float32
    if cfg.family in ("dense", "vlm", "moe"):
        shapes, axes = A.make_kv_cache_specs(cfg, batch, cache_len, dtype)
        Lx = cfg.num_layers
        stacked = tuple(jax.ShapeDtypeStruct((Lx,) + s.shape, s.dtype) for s in shapes)
        st_axes = tuple(("stack",) + a for a in axes)
        return {"kv": stacked}, {"kv": st_axes}
    if cfg.family == "ssm":
        shapes, axes = S.mamba_state_specs(cfg, batch, sdtype)
        Lx = cfg.num_layers
        stacked = tuple(jax.ShapeDtypeStruct((Lx,) + s.shape, s.dtype) for s in shapes)
        st_axes = tuple(("stack",) + a for a in axes)
        return {"ssm": stacked}, {"ssm": st_axes}
    if cfg.family == "hybrid":
        sshapes, saxes = S.mamba_state_specs(cfg, batch, sdtype)
        Lx = cfg.num_layers
        ssm_stacked = tuple(jax.ShapeDtypeStruct((Lx,) + s.shape, s.dtype) for s in sshapes)
        ssm_axes = tuple(("stack",) + a for a in saxes)
        period = cfg.hybrid_attn_every or cfg.num_layers
        n_sb = cfg.num_layers // period
        win = cfg.sliding_window or cache_len
        attn_len = min(cache_len, win)
        kshapes, kaxes = A.make_kv_cache_specs(cfg, batch, attn_len, dtype)
        kv_stacked = tuple(jax.ShapeDtypeStruct((n_sb,) + s.shape, s.dtype) for s in kshapes)
        kv_axes = tuple(("stack",) + a for a in kaxes)
        return {"ssm": ssm_stacked, "kv": kv_stacked}, {"ssm": ssm_axes, "kv": kv_axes}
    if cfg.family == "audio":
        kshapes, kaxes = A.make_kv_cache_specs(cfg, batch, cache_len, dtype)
        Lx = cfg.num_layers
        self_kv = tuple(jax.ShapeDtypeStruct((Lx,) + s.shape, s.dtype) for s in kshapes)
        self_axes = tuple(("stack",) + a for a in kaxes)
        # cross-attention K/V, projected ONCE from the encoder output at
        # prefill (seed_audio_caches) and read-only during decode — replaces
        # the old raw ``enc_out`` leaf that forced a re-projection per step
        KH, hd, Se = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.encoder_seq
        cshapes = [jax.ShapeDtypeStruct((Lx, batch, Se, KH, hd), dtype)] * 2
        caxes = [("stack", "batch", None, "kv_heads", None)] * 2
        if is_int8(dtype):
            cshapes += [jax.ShapeDtypeStruct((Lx, batch, Se, KH), jnp.float32)] * 2
            caxes += [("stack", "batch", None, "kv_heads")] * 2
        return (
            {"kv": self_kv, "cross": tuple(cshapes)},
            {"kv": self_axes, "cross": tuple(caxes)},
        )
    raise ValueError(cfg.family)


def init_decode_caches(cfg: ModelConfig, batch: int, cache_len: int, dtype=jnp.bfloat16):
    """Concrete zero caches with the position track set to the INT32_MAX
    sentinel so unwritten slots never pass the causal mask."""
    sds, _ = make_decode_caches(cfg, batch, cache_len, dtype)

    def init_one(s):
        if s.dtype == jnp.int32:
            return jnp.full(s.shape, jnp.iinfo(jnp.int32).max, jnp.int32)
        return jnp.zeros(s.shape, s.dtype)

    return jax.tree.map(init_one, sds, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def decode_step(cfg: ModelConfig, params, tokens, caches, index, force_window=False,
                fresh_cache=False):
    """One cache-threading forward: single decode token OR a whole prefill block.

    tokens: [B, S] token ids (S == 1 for classic decode). ``index`` is either
    a scalar cache write position — the S tokens land contiguously at
    [index, index + S) with ONE ``dynamic_update_slice`` per layer (batched
    single-pass prefill) — or an int32 [B] vector of per-slot positions
    (the serving engine's continuous batching, where freed slots sit at
    different depths; S > 1 with a vector index is the speculative verify
    block — each row writes S tokens at [index[b], index[b] + S)).
    ``fresh_cache`` (static) asserts nothing precedes this write in the
    cache, routing long prefill blocks through the flash attention path
    instead of cache-wide scores.

    Returns (logits [B, S, V], new_caches).
    """
    B, S = tokens.shape
    x = embed_tokens(cfg, params, tokens)
    if jnp.ndim(index) == 1:
        if S != 1 and cfg.family == "hybrid":
            # ring-buffer attention caches wrap write positions with a
            # remainder; the vector multi-token write drops instead of
            # wrapping, so spans crossing the ring edge would be lost
            raise ValueError("hybrid ring caches take single-token vector writes only")
        positions = jnp.asarray(index, jnp.int32)[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    else:
        positions = jnp.broadcast_to(
            jnp.asarray(index, jnp.int32) + jnp.arange(S, dtype=jnp.int32), (B, S)
        )
    windows = layer_windows(cfg, cfg.num_layers, force_window)

    if cfg.family in ("dense", "vlm"):
        x, new_kv = dense_stack_decode(params["layers"], x, positions, cfg, windows,
                                       caches["kv"], index, fresh_cache)
        new_caches = {"kv": new_kv}
    elif cfg.family == "moe":
        nd = cfg.first_dense_layers
        kv = caches["kv"]
        if nd:
            head_kv = jax.tree.map(lambda a: a[:nd], kv)
            tail_kv = jax.tree.map(lambda a: a[nd:], kv)
            x, new_head = dense_stack_decode(params["dense_layers"], x, positions, cfg,
                                             windows[:nd], head_kv, index, fresh_cache)
            x, new_tail = moe_stack_decode(params["layers"], x, positions, cfg,
                                           windows[nd:], tail_kv, index, fresh_cache)
            new_kv = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0), new_head, new_tail)
        else:
            x, new_kv = moe_stack_decode(params["layers"], x, positions, cfg, windows,
                                         kv, index, fresh_cache)
        new_caches = {"kv": new_kv}
    elif cfg.family == "ssm":
        x, new_ssm = mamba_stack_decode(params["layers"], x, cfg, caches["ssm"])
        new_caches = {"ssm": new_ssm}
    elif cfg.family == "hybrid":
        x, new_caches = _hybrid_decode(cfg, params, x, positions, caches, index)
    elif cfg.family == "audio":
        x, new_caches = _audio_decode(cfg, params, x, positions, caches, index, fresh_cache)
    else:
        raise ValueError(cfg.family)

    x = L.apply_norm(cfg.norm, params["final_norm"], x)
    return logits_from_hidden(cfg, params, x), new_caches


def supports_self_speculation(cfg: ModelConfig) -> bool:
    """Self-speculative decoding needs (a) a homogeneous stacked layer scan to
    truncate and (b) caches that can be safely overwritten on rejection.
    Attention caches qualify — a rejected slot is rewritten before it is ever
    attended (writes precede reads and positions advance monotonically) — but
    SSM/hybrid recurrent state cannot roll back, so those families are out.
    """
    return cfg.family in ("dense", "vlm", "moe")


def draft_decode_step(cfg: ModelConfig, params, tokens, caches, index, draft_layers: int):
    """Truncated-depth (early-exit self-speculative) draft pass.

    Runs only the FIRST ``draft_layers`` of the stacked scan and reads draft
    logits off the shared residual trunk (final_norm + lm head). tokens:
    [B, 1]; ``index``: int32 [B] per-slot write positions. The draft's cache
    writes for layers < draft_layers are identical to what the verify pass
    will rewrite (same trunk, same inputs), so speculation never corrupts the
    cache. Returns (logits [B, 1, V], new_caches) with the updated layer-head
    caches spliced back into the full stack.
    """
    if not supports_self_speculation(cfg):
        raise ValueError(f"self-speculation unsupported for family {cfg.family!r}")
    if not (0 < draft_layers < cfg.num_layers):
        raise ValueError(f"draft_layers must be in (0, {cfg.num_layers}), got {draft_layers}")
    B, S = tokens.shape
    x = embed_tokens(cfg, params, tokens)
    positions = jnp.asarray(index, jnp.int32)[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    windows = layer_windows(cfg, cfg.num_layers)
    kv = caches["kv"]
    head_kv = jax.tree.map(lambda a: a[:draft_layers], kv)

    if cfg.family in ("dense", "vlm"):
        sub = jax.tree.map(lambda a: a[:draft_layers], params["layers"])
        x, new_head = dense_stack_decode(sub, x, positions, cfg, windows[:draft_layers],
                                         head_kv, index)
    else:  # moe: dense head layers first, then truncated moe stack
        nd = cfg.first_dense_layers
        k1 = min(draft_layers, nd)
        new_parts = []
        if k1:
            sub = jax.tree.map(lambda a: a[:k1], params["dense_layers"])
            x, nh = dense_stack_decode(sub, x, positions, cfg, windows[:k1],
                                       jax.tree.map(lambda a: a[:k1], head_kv), index)
            new_parts.append(nh)
        k2 = draft_layers - k1
        if k2:
            sub = jax.tree.map(lambda a: a[:k2], params["layers"])
            x, nt = moe_stack_decode(sub, x, positions, cfg, windows[nd : nd + k2],
                                     jax.tree.map(lambda a: a[k1:], head_kv), index)
            new_parts.append(nt)
        new_head = (
            jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0), *new_parts)
            if len(new_parts) > 1 else new_parts[0]
        )

    new_kv = jax.tree.map(
        lambda full, nh: jax.lax.dynamic_update_slice_in_dim(full, nh, 0, axis=0),
        kv, new_head,
    )
    x = L.apply_norm(cfg.norm, params["final_norm"], x)
    return logits_from_hidden(cfg, params, x), {"kv": new_kv}


def _hybrid_decode(cfg, params, x, positions, caches, index):
    period = cfg.hybrid_attn_every or cfg.num_layers
    n_sb = cfg.num_layers // period
    win = cfg.sliding_window or 0
    attn_len = caches["kv"][0].shape[2]
    widx = jnp.remainder(index, attn_len) if win else index
    new_ssm, new_kv = [], []
    ssm, kv = caches["ssm"], caches["kv"]
    for i in range(n_sb):
        sb = jax.tree.map(lambda a: jax.lax.slice_in_dim(a, i * period, (i + 1) * period, axis=0), params["layers"])
        st = jax.tree.map(lambda a: jax.lax.slice_in_dim(a, i * period, (i + 1) * period, axis=0), ssm)
        x, st_new = mamba_stack_decode(sb, x, cfg, st)
        new_ssm.append(st_new)
        cache_i = jax.tree.map(lambda a: a[i], kv)
        x, kv_new = _shared_attn_decode(cfg, params["shared_attn"], x, positions, cache_i, widx, win)
        new_kv.append(kv_new)
    rem = cfg.num_layers - n_sb * period
    if rem:
        sb = jax.tree.map(lambda a: jax.lax.slice_in_dim(a, n_sb * period, cfg.num_layers, axis=0), params["layers"])
        st = jax.tree.map(lambda a: jax.lax.slice_in_dim(a, n_sb * period, cfg.num_layers, axis=0), ssm)
        x, st_new = mamba_stack_decode(sb, x, cfg, st)
        new_ssm.append(st_new)
    new_ssm = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *new_ssm) if len(new_ssm) > 1 else new_ssm[0]
    new_kv = jax.tree.map(lambda *xs: jnp.stack(xs, 0), *new_kv)
    return x, {"ssm": new_ssm, "kv": new_kv}


def _shared_attn_decode(cfg, p, x, positions, cache, write_idx, window):
    h = L.apply_norm(cfg.norm, p["norm1"], x)
    a, new_cache = A.gqa_forward(p["attn"], h, positions, cfg, window=window, kv_cache=cache, cache_index=write_idx)
    x = x + a
    h = L.apply_norm(cfg.norm, p["norm2"], x)
    x = x + mlp_forward(p["mlp"], h, cfg)
    return x, new_cache


def _audio_decode(cfg, params, x, positions, caches, index, fresh_cache=False):
    cross = caches["cross"]
    quant = len(cross) == 4

    def body(xc, layer):
        p_self, p_cross, cache = layer[:3]
        if quant:
            ck = dequantize_rows(layer[3], layer[5], xc.dtype)
            cv = dequantize_rows(layer[4], layer[6], xc.dtype)
        else:
            ck, cv = layer[3], layer[4]
        h = L.apply_norm(cfg.norm, p_self["norm1"], xc)
        a, new_cache = A.gqa_forward(p_self["attn"], h, positions, cfg, window=0,
                                     kv_cache=cache, cache_index=index, fresh_cache=fresh_cache)
        xc = xc + a
        h = L.apply_norm(cfg.norm, p_self["norm2"], xc)
        xc = xc + mlp_forward(p_self["mlp"], h, cfg)
        h = L.apply_norm(cfg.norm, p_cross["norm1"], xc)
        c = cross_attention_cached(p_cross["attn"], h, ck, cv, cfg)
        xc = xc + c
        h = L.apply_norm(cfg.norm, p_cross["norm2"], xc)
        xc = xc + mlp_forward(p_cross["mlp"], h, cfg)
        return xc, new_cache

    xs = (params["layers"], params["cross_layers"], caches["kv"]) + tuple(cross)
    x, new_kv = jax.lax.scan(body, x, xs)
    return x, {"kv": new_kv, "cross": cross}
