"""Plain reference of the stablelm dense decoder as the program runs it, and
of one llm_hybrid C-HSGD round over it.

Decoder layer (pre-norm): h = LayerNorm(x) (eps 1e-5, scale and bias);
q, k, v = h Wq, h Wk, h Wv per head; rotary position embedding over the
whole head dimension (pairs (i, i + head_dim/2), base ``rope_theta``);
causal softmax(q k^T / sqrt(head_dim)) v; x += heads Wo; h = LayerNorm(x);
x += (silu(h Wgate) * (h Wup)) Wdown. Token embeddings are scaled by
sqrt(hidden_size). Departures from the published stablelm-2 (quarter-width
rotary, q/k/v biases, no embedding scale) are the program's and are followed
here; the configuration file lists them.

llm_hybrid (the paper's hybrid split over the sequence): the hospital tower
theta1 and the device tower theta2 each embed their half of the sequence and
run their own layers and final LayerNorm; the combined model theta0 runs its
layers over [zeta1, zeta2], a final LayerNorm and the vocabulary head, with
the mean cross entropy over every position as the loss. A round of P steps
with an exchange every Q: zeta1, zeta2 and a theta0 snapshot are
compressed (``compress.py``); the hospital steps (theta0, theta1) with fresh
zeta1 and stale zeta2, the device steps theta2 with the stale theta0 and
zeta1, both by plain SGD.

Layers run under ``jax.checkpoint`` and the cross entropy in sequence chunks,
so that the reference fits beside the weights; neither changes a result.
float32 runs at `highest` matmul precision.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

import compress as C

EPS = 1e-5
CE_CHUNK = 256


def layer_norm(p, x):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) / jnp.sqrt(var + EPS) * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def rotary(x, theta: float):
    """x [B, S, H, D]: rotate pairs (i, i + D/2) by position * theta^(-2i/D)."""
    S, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    xf = x.astype(jnp.float32)
    half = D // 2
    rot = jnp.concatenate([-xf[..., half:], xf[..., :half]], axis=-1)
    return (xf * cos + rot * sin).astype(x.dtype)


def attention(p, h, theta):
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
    q, k = rotary(q, theta), rotary(k, theta)
    S, D = h.shape[1], q.shape[-1]
    s = jnp.einsum("bqhk,bshk->bhqs", q, k).astype(jnp.float32) / jnp.sqrt(jnp.float32(D))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1).astype(h.dtype)
    o = jnp.einsum("bhqs,bshk->bqhk", a, v)
    return jnp.einsum("bqhk,hkd->bqd", o, p["wo"])


def layer(p, x, theta):
    x = x + attention(p["attn"], layer_norm(p["norm1"], x), theta)
    h = layer_norm(p["norm2"], x)
    m = p["mlp"]
    return x + (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def layers(stack, x, theta):
    n = jax.tree_util.tree_leaves(stack)[0].shape[0]
    f = jax.checkpoint(lambda p, x: layer(p, x, theta))
    for i in range(n):
        x = f(jax.tree.map(lambda a: a[i], stack), x)
    return x


def embed(table, tokens):
    x = table[tokens]
    return x * jnp.sqrt(jnp.float32(table.shape[-1])).astype(x.dtype)


def tower(p, tokens, theta):
    return layer_norm(p["norm"], layers(p["layers"], embed(p["embed"]["table"], tokens), theta))


def mean_xent(hidden, head_w, y):
    """Mean cross entropy of hidden @ head_w against y, in sequence chunks."""
    B, S, D = hidden.shape
    c = min(CE_CHUNK, S)
    if S % c:
        raise ValueError(f"sequence {S} is not a multiple of the chunk {c}")
    n = S // c
    hc = hidden.reshape(B, n, c, D).swapaxes(0, 1)
    yc = y.reshape(B, n, c).swapaxes(0, 1)

    @jax.checkpoint
    def chunk(h, t):
        logits = (h @ head_w).astype(jnp.float32)
        picked = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    total = sum(chunk(hc[i], yc[i]) for i in range(n))
    return total / (B * S)


def combined_loss(t0, z1, z2, y, theta):
    x = layers(t0["layers"], jnp.concatenate([z1, z2], axis=1), theta)
    return mean_xent(layer_norm(t0["final_norm"], x), t0["head"]["w"], y)


def _norms(tree):
    return jax.tree.map(lambda g: jnp.linalg.norm(g.astype(jnp.float32)), tree)


def _sgd(p, g, lr):
    return jax.tree.map(lambda a, b: a - jnp.asarray(lr, a.dtype) * b, p, g)


def make_round(theta: float, k_frac: float, levels: int):
    @jax.jit
    def zetas(t1, t2, x1, x2):
        return tower(t1, x1, theta), tower(t2, x2, theta)

    @partial(jax.jit, donate_argnums=(0, 1))
    def hospital_step(t0, t1, x1, z2, y, lr):
        loss_fn = lambda a, b: combined_loss(a, tower(b, x1, theta), z2, y, theta)
        loss, (g0, g1) = jax.value_and_grad(loss_fn, argnums=(0, 1))(t0, t1)
        return _sgd(t0, g0, lr), _sgd(t1, g1, lr), loss, (_norms(g0), _norms(g1))

    @partial(jax.jit, donate_argnums=(0,))
    def device_step(t2, x2, y, t0_stale, z1, lr):
        loss_fn = lambda c: combined_loss(t0_stale, z1, tower(c, x2, theta), y, theta)
        g2 = jax.grad(loss_fn)(t2)
        return _sgd(t2, g2, lr), _norms(g2)

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
            stale = {"theta0": C.compress_tree(t0, k_frac, levels),
                     "z1": C.compress_tree(z1, k_frac, levels),
                     "z2": C.compress_tree(z2, k_frac, levels)}
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


def round_readings(make_weights, batches, theta, P, Q, k_frac, levels, lr,
                   dtype=jnp.float32, fault=None):
    """(losses [P], per-leaf norm of the weights' change, per-leaf norm of
    the first step's gradient). ``make_weights()`` returns the starting
    weights afresh each call, so they need not be held through the round."""
    prec = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        w = make_weights()
        if dtype != jnp.float32:
            w = jax.jit(lambda w: jax.tree.map(lambda x: x.astype(dtype), w))(w)
        after, losses, gnorm = make_round(theta, k_frac, levels)(w, batches, P, Q, lr, fault)
    del w
    w0 = make_weights()
    change = jax.tree.map(
        lambda a, b: jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32)),
        after, w0)
    return jax.device_get((losses, change, gnorm))


def decoder_logits(params, tokens, positions, theta: float):
    """Logits at ``positions`` of the plain decoder over ``tokens`` [S]:
    embedding, the layers, final LayerNorm and the head (``params["head"]``,
    or the embedding table when tied). Causal, so padding after the last
    position read changes nothing."""
    x = embed(params["embed"]["table"], tokens[None])
    x = layer_norm(params["final_norm"], layers(params["layers"], x, theta))[0]
    h = x[positions]
    w = params["head"]["w"] if "head" in params else params["embed"]["table"].T
    return (h @ w.astype(h.dtype)).astype(jnp.float32)


def served_gaps(params, requests, theta: float, length: int, max_new: int,
                control_dtype=None):
    """For each (prompt, served tokens): the widest gap by which a served
    token's logit lies below the reference's best at its position. With
    ``control_dtype`` the reference is also run in that precision and the gap
    is read at the token the lower precision puts first (the control).
    Sequences are padded to ``length`` and positions to ``max_new`` so one
    program serves every request."""
    import numpy as np

    f32 = jax.jit(partial(decoder_logits, theta=theta))
    low = None
    if control_dtype is not None:
        cast = jax.jit(lambda p: jax.tree.map(lambda x: x.astype(control_dtype), p))
        low_params = cast(params)
        low = jax.jit(partial(decoder_logits, theta=theta))
    out = []
    for prompt, served in requests:
        seq = np.zeros(length, np.int32)
        full = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        seq[: full.size] = full
        pos = np.full(max_new, len(prompt) - 1, np.int32)
        pos[: len(served)] = len(prompt) - 1 + np.arange(len(served))
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(f32(params, jnp.asarray(seq), jnp.asarray(pos)))[: len(served)]
        best = ref.max(axis=-1)
        if low is None:
            picked = ref[np.arange(len(served)), served]
        else:
            lo = np.asarray(low(low_params, jnp.asarray(seq), jnp.asarray(pos)))[: len(served)]
            picked = ref[np.arange(len(served)), lo.argmax(axis=-1)]
        out.append(float(np.max(best - picked)))
    return out
