"""Plain reference of the published stablelm-2 decoder
(https://huggingface.co/stabilityai/stablelm-2-1_6b, ``StableLmForCausalLM``)
and of one llm_hybrid HSGD round over it.

Decoder layer (pre-norm, sequential residual): h = LayerNorm(x) (eps 1e-5,
scale and bias); q = h Wq + bq, k = h Wk + bk, v = h Wv + bv per head
(``use_qkv_bias``); rotary position embedding over the first
``partial_rotary_factor`` of each head's channels only (``rot`` channels,
pairs (i, i + rot/2), inverse frequencies base^(-2i/rot)), the others
passed through; causal softmax(q k^T / sqrt(head_dim)) v; x += heads Wo (no
bias); h = LayerNorm(x); x += (silu(h Wgate) * (h Wup)) Wdown. Token
embeddings enter the first layer unscaled. The head is untied.

The round (the paper's hybrid split over the sequence, plain SGD, an
exchange every Q of P steps, ``compress.py`` on the exchanged messages) and
its readings are those of ``stablelm.py``, whose LayerNorm, chunked cross
entropy and SGD this module reuses; only the decoder differs. The compiled
steps of a round are kept per (theta, rot, k, levels), so that a process
that checks several seeds compiles them once. float32 runs at `highest`
matmul precision.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp

import compress as C
from stablelm import _norms, _sgd, layer_norm, mean_xent


def rotary(x, theta: float, rot: int):
    """x [B, S, H, D]: rotate the first ``rot`` channels of each head, pairs
    (i, i + rot/2) by position * theta^(-2i/rot); the rest unchanged."""
    S = x.shape[1]
    inv = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    xf = x.astype(jnp.float32)
    xr, xp = xf[..., :rot], xf[..., rot:]
    half = rot // 2
    turned = jnp.concatenate([-xr[..., half:], xr[..., :half]], axis=-1)
    return jnp.concatenate([xr * cos + turned * sin, xp], axis=-1).astype(x.dtype)


def attention(p, h, theta, rot):
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"]) + p["q_bias"]
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"]) + p["k_bias"]
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"]) + p["v_bias"]
    q, k = rotary(q, theta, rot), rotary(k, theta, rot)
    S, D = h.shape[1], q.shape[-1]
    s = jnp.einsum("bqhk,bshk->bhqs", q, k).astype(jnp.float32) / jnp.sqrt(jnp.float32(D))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1).astype(h.dtype)
    o = jnp.einsum("bhqs,bshk->bqhk", a, v)
    return jnp.einsum("bqhk,hkd->bqd", o, p["wo"])


def layer(p, x, theta, rot):
    x = x + attention(p["attn"], layer_norm(p["norm1"], x), theta, rot)
    h = layer_norm(p["norm2"], x)
    m = p["mlp"]
    return x + (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def layers(stack, x, theta, rot):
    n = jax.tree_util.tree_leaves(stack)[0].shape[0]
    f = jax.checkpoint(lambda p, x: layer(p, x, theta, rot))
    for i in range(n):
        x = f(jax.tree.map(lambda a: a[i], stack), x)
    return x


def tower(p, tokens, theta, rot):
    x = p["embed"]["table"][tokens]
    return layer_norm(p["norm"], layers(p["layers"], x, theta, rot))


def combined_loss(t0, z1, z2, y, theta, rot):
    x = layers(t0["layers"], jnp.concatenate([z1, z2], axis=1), theta, rot)
    return mean_xent(layer_norm(t0["final_norm"], x), t0["head"]["w"], y)


@lru_cache(maxsize=None)
def make_round(theta: float, rot: int, k_frac: float, levels: int):
    @jax.jit
    def zetas(t1, t2, x1, x2):
        return tower(t1, x1, theta, rot), tower(t2, x2, theta, rot)

    @partial(jax.jit, donate_argnums=(0, 1))
    def hospital_step(t0, t1, x1, z2, y, lr):
        loss_fn = lambda a, b: combined_loss(a, tower(b, x1, theta, rot), z2, y, theta, rot)
        loss, (g0, g1) = jax.value_and_grad(loss_fn, argnums=(0, 1))(t0, t1)
        return _sgd(t0, g0, lr), _sgd(t1, g1, lr), loss, (_norms(g0), _norms(g1))

    @partial(jax.jit, donate_argnums=(0,))
    def device_step(t2, x2, y, t0_stale, z1, lr):
        loss_fn = lambda c: combined_loss(t0_stale, z1, tower(c, x2, theta, rot), y, theta, rot)
        g2 = jax.grad(loss_fn)(t2)
        return _sgd(t2, g2, lr), _norms(g2)

    compress = partial(C.compress_tree, k_frac=k_frac, levels=levels)

    def round_(w, batches, P: int, Q: int, lr: float, fault=None):
        """w: {theta0, theta1, theta2}; batches: one {x1, x2, y} per interval.
        ``fault="half_batch"`` leaves out the second half of every batch."""
        t0, t1, t2 = w["theta0"], w["theta1"], w["theta2"]
        del w
        losses, gnorm = [], None
        for b in batches[: P // Q]:
            if fault == "half_batch":
                b = jax.tree.map(lambda x: x[: x.shape[0] // 2], b)
            z1, z2 = zetas(t1, t2, b["x1"], b["x2"])
            stale = {"theta0": compress(t0), "z1": compress(z1), "z2": compress(z2)}
            del z1, z2
            for _ in range(Q):
                t0, t1, loss, (n0, n1) = hospital_step(t0, t1, b["x1"], stale["z2"], b["y"], lr)
                t2, n2 = device_step(t2, b["x2"], b["y"], stale["theta0"], stale["z1"], lr)
                losses.append(loss)
                if gnorm is None:
                    gnorm = {"theta0": n0, "theta1": n1, "theta2": n2}
            del stale
        return {"theta0": t0, "theta1": t1, "theta2": t2}, jnp.stack(losses), gnorm

    return round_


def round_readings(make_weights, batches, theta, rot, P, Q, k_frac, levels, lr,
                   dtype=jnp.float32, fault=None):
    """(losses [P], per-leaf norm of the weights' change, per-leaf norm of
    the first step's gradient). ``make_weights()`` returns the starting
    weights afresh each call, so they need not be held through the round."""
    prec = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        w = make_weights()
        if dtype != jnp.float32:
            w = jax.jit(lambda w: jax.tree.map(lambda x: x.astype(dtype), w))(w)
        round_ = make_round(float(theta), int(rot), float(k_frac), int(levels))
        after, losses, gnorm = round_(w, batches, P, Q, lr, fault)
    del w
    w0 = make_weights()
    change = jax.tree.map(
        lambda a, b: jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32)),
        after, w0)
    return jax.device_get((losses, change, gnorm))


def decoder_logits(params, tokens, theta: float, rot: int):
    """Logits [S, V] of the plain decoder over ``tokens`` [S]: embedding, the
    layers, final LayerNorm and the untied head."""
    x = params["embed"]["table"][tokens[None]]
    x = layer_norm(params["final_norm"], layers(params["layers"], x, theta, rot))[0]
    return (x @ params["head"]["w"]).astype(jnp.float32)
