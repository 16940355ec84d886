"""Step builders + input specs for every (architecture × input shape), and
the LLM-scale compiled federated runner built from them.

Three lowered programs per training shape (their roofline terms combine as
  cost/step = train_step + (1/Q)·exchange_step + (1/P)·global_agg
— exactly the paper's C(P,Q) decomposition):

  * ``hsgd_train_step``  — one HSGD iteration (eqs. 5–7): hospital update with
    fresh ζ1/stale ζ2, device update with stale θ0/ζ1. Runs every step, no
    cross-tier communication beyond the within-group batch reduce.
  * ``exchange_step``    — recompute + exchange ζ1, ζ2 and snapshot θ0
    (fired every Q steps; optionally top-k compressed).
  * ``global_agg``       — eq. (2) across groups (pods), fired every P steps.

Inference shapes lower the plain architecture (federation is a training
construct): ``prefill_step`` and ``decode_step``.

TPU adaptation of tier-1 (documented in DESIGN §2): the within-group device
aggregation (eq. 1) is realized by the batch-mean over the data axis that the
gradient computation already performs — on a pod this reduction is the
standard within-replica gradient sync, so Q amortizes the *vertical exchange*
while P amortizes the *cross-pod model sync*.

``LLMRoundRunner`` assembles those three programs into ONE donating, jitted,
scan-based executor per (P, Q, k, b) bucket — the LLM-scale mirror of
``core/hsgd.HSGDRunner.round_fn`` — and ``AdaptiveLLMRunner`` drives the §VI
plan/probe/governor loop (``core/controller.ControllerCore``) over those
compiled rounds, closing the adaptive loop on the ``llm_hybrid`` path.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.config import FederationConfig, InputShape, ModelConfig
from repro.common.sharding import DEFAULT_RULES, divisible_spec, logical_to_spec
from repro.common.pytree import tree_dot, tree_norm, tree_size, tree_sub
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.split_model import HybridModel, llm_hybrid

VIS_PATCHES = 1024  # stubbed vision patches prepended for the VLM arch

# long_500k needs sub-quadratic attention: run only where that holds.
LONG_CTX_OK = {"gemma3-1b", "gemma3-4b", "zamba2-2.7b", "falcon-mamba-7b"}


# ---------------------------------------------------------------------------
# Sharding helpers
# ---------------------------------------------------------------------------


def build_shardings(shapes_tree, axes_tree, mesh: Mesh, rules=None):
    """ShapeDtypeStruct tree + logical-axes tree -> NamedSharding tree."""
    rules = rules or DEFAULT_RULES

    def one(sds, axes):
        if axes is None:
            return NamedSharding(mesh, P())
        spec = logical_to_spec(axes, rules, mesh)
        spec = divisible_spec(sds.shape, spec, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree.map(
        one, shapes_tree, axes_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# Input specs
# ---------------------------------------------------------------------------


def hybrid_train_inputs(cfg: ModelConfig, shape: InputShape):
    """ShapeDtypeStructs + logical axes for the HSGD training batch."""
    B, S = shape.global_batch, shape.seq_len
    dt = _dtype(cfg)
    tok_axes = ("batch", "seq")
    emb_axes = ("batch", "seq", None)
    if cfg.family == "vlm":
        pv = VIS_PATCHES
        sds = {
            "x1": jax.ShapeDtypeStruct((B, pv, cfg.d_model), dt),
            "x2": jax.ShapeDtypeStruct((B, S - pv), jnp.int32),
            "y": jax.ShapeDtypeStruct((B, S - pv), jnp.int32),
        }
        axes = {"x1": emb_axes, "x2": tok_axes, "y": tok_axes}
    elif cfg.family == "audio":
        sds = {
            "x1": jax.ShapeDtypeStruct((B, cfg.encoder_seq, cfg.d_model), dt),
            "x2": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "y": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
        axes = {"x1": emb_axes, "x2": tok_axes, "y": tok_axes}
    else:
        s1 = S // 2
        sds = {
            "x1": jax.ShapeDtypeStruct((B, s1), jnp.int32),
            "x2": jax.ShapeDtypeStruct((B, S - s1), jnp.int32),
            "y": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
        axes = {"x1": tok_axes, "x2": tok_axes, "y": tok_axes}
    return sds, axes


def hybrid_stale_inputs(model: HybridModel, cfg: ModelConfig, batch_sds):
    """Shapes of the stale exchange context (ζ1, ζ2, θ0 snapshot)."""
    dt = _dtype(cfg)
    t1 = L.abstract_params(model.specs1, dt)
    t2 = L.abstract_params(model.specs2, dt)
    z1 = jax.eval_shape(model.h1, t1, batch_sds["x1"])
    z2 = jax.eval_shape(model.h2, t2, batch_sds["x2"])
    t0 = L.abstract_params(model.specs0, dt)
    sds = {"theta0": t0, "z1": z1, "z2": z2}
    axes = {
        "theta0": L.axes_tree(model.specs0),
        "z1": ("batch", "seq", None),
        "z2": ("batch", "seq", None),
    }
    return sds, axes


def inference_inputs(cfg: ModelConfig, shape: InputShape, force_window: bool):
    """(prefill | decode) inputs for the plain architecture."""
    B, S = shape.global_batch, shape.seq_len
    dt = _dtype(cfg)
    if shape.kind == "prefill":
        sds: Dict[str, Any] = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        axes: Dict[str, Any] = {"tokens": ("batch", "seq")}
        if cfg.family == "vlm":
            sds["tokens"] = jax.ShapeDtypeStruct((B, S - VIS_PATCHES), jnp.int32)
            sds["extra_embeds"] = jax.ShapeDtypeStruct((B, VIS_PATCHES, cfg.d_model), dt)
            axes["extra_embeds"] = ("batch", "seq", None)
        elif cfg.family == "audio":
            sds["extra_embeds"] = jax.ShapeDtypeStruct((B, cfg.encoder_seq, cfg.d_model), dt)
            axes["extra_embeds"] = ("batch", "seq", None)
        return sds, axes
    # decode: one token + caches
    cache_len = S
    if force_window and cfg.sliding_window:
        cache_len = min(S, cfg.sliding_window)
    cache_sds, cache_axes = T.make_decode_caches(cfg, B, cache_len, dt)
    sds = {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32), "caches": cache_sds}
    axes = {"tokens": ("batch", None), "caches": cache_axes}
    return sds, axes


# ---------------------------------------------------------------------------
# HSGD step builders (training shapes)
# ---------------------------------------------------------------------------


def make_hybrid(cfg: ModelConfig, n_tower: int = 2, remat: bool = True) -> HybridModel:
    return llm_hybrid(cfg, n_tower=n_tower, remat=remat)


def hybrid_grads(model: HybridModel, params, stale, batch):
    """The eqs. (5)–(7) gradients for one worker: hospital (θ0, θ1) with fresh
    ζ1/stale ζ2, device θ2 with stale θ0/ζ1. Shared by the plain train step
    and the probe-collecting stats step. Each gradient runs under its phase's
    scope of ``core/hsgd.PHASE_SCOPES``."""

    def hosp_loss(t0, t1):
        z1 = model.h1(t1, batch["x1"])
        return model.loss(t0, z1, jax.lax.stop_gradient(stale["z2"]), batch["y"])

    with jax.named_scope("local_step/hospital"):
        loss, (g0, g1) = jax.value_and_grad(hosp_loss, argnums=(0, 1))(
            params["theta0"], params["theta1"]
        )

    def dev_loss(t2):
        z2 = model.h2(t2, batch["x2"])
        return model.loss(
            jax.lax.stop_gradient(stale["theta0"]),
            jax.lax.stop_gradient(stale["z1"]),
            z2,
            batch["y"],
        )

    with jax.named_scope("local_step/device"):
        g2 = jax.grad(dev_loss)(params["theta2"])
    return loss, {"theta0": g0, "theta1": g1, "theta2": g2}


def _apply_update(params, grads, lr):
    """SGD on {θ0, θ1, θ2}: the hospital update (eqs. 5–6) and the device
    update (eq. 7), each under its phase's scope."""
    upd = lambda p, g: p - lr * g.astype(p.dtype)
    with jax.named_scope("local_step/hospital"):
        theta0 = jax.tree.map(upd, params["theta0"], grads["theta0"])
        theta1 = jax.tree.map(upd, params["theta1"], grads["theta1"])
    with jax.named_scope("local_step/device"):
        theta2 = jax.tree.map(upd, params["theta2"], grads["theta2"])
    return {"theta0": theta0, "theta1": theta1, "theta2": theta2}


def make_hsgd_train_step(model: HybridModel, lr: float = 1e-3) -> Callable:
    """step(params, stale, batch, lr=lr) — ``lr`` may be a traced scalar, so
    the adaptive runner re-picks η without recompiling."""

    def step(params, stale, batch, lr=lr):
        loss, grads = hybrid_grads(model, params, stale, batch)
        return _apply_update(params, grads, lr), loss

    return step


def make_hsgd_step_stats(model: HybridModel, n_shards: int = 2) -> Callable:
    """Probe-collecting twin of ``make_hsgd_train_step`` (the LLM-path
    analogue of ``core/hsgd.local_sgd_step_stats``).

    The mini-batch is split into ``n_shards`` equal worker shards along the
    batch axis; each shard's eqs. (5)–(7) gradients are computed and averaged,
    which IS the full-batch gradient (the losses are example means), so the
    parameter update is unchanged while the per-shard spread yields the §VI-B
    δ² estimate for free. Returns (new_params, loss, {gbar, gnorm2, delta2}).
    """

    def step(params, stale, batch, lr):
        B = batch["y"].shape[0]
        if n_shards > 1 and B % n_shards:
            # a silent 1-shard fallback would make δ² identically zero and
            # the controller would stop adapting to gradient noise unnoticed
            raise ValueError(
                f"probe-collecting step needs batch size divisible by "
                f"n_shards={n_shards}, got {B}")
        ns = n_shards
        split = lambda x: x.reshape((ns, x.shape[0] // ns) + x.shape[1:])

        def shard_grads(z1_s, z2_s, batch_s):
            stale_s = {"theta0": stale["theta0"], "z1": z1_s, "z2": z2_s}
            return hybrid_grads(model, params, stale_s, batch_s)

        losses, g = jax.vmap(shard_grads)(
            split(stale["z1"]), split(stale["z2"]), jax.tree.map(split, batch))
        gbar = jax.tree.map(lambda x: jnp.mean(x.astype(jnp.float32), axis=0), g)
        dev = jax.tree.map(
            lambda x, m: jnp.sum((x.astype(jnp.float32) - m[None]) ** 2,
                                 axis=tuple(range(1, x.ndim))), g, gbar)
        delta2 = jnp.mean(sum(jax.tree_util.tree_leaves(dev)))
        new = _apply_update(params, gbar, lr)
        aux = {"gbar": gbar, "gnorm2": tree_dot(gbar, gbar), "delta2": delta2}
        return new, jnp.mean(losses), aux

    return step


def make_exchange_step(model: HybridModel, compression_k: float = 0.0, quant: int = 0,
                       dp: bool = False) -> Callable:
    """ζ1/ζ2 recompute + θ0 snapshot — the C-HSGD wire message.

    The WHOLE {θ0, ζ1, ζ2} message is compressed in one ``compress_pytree``
    call, matching ``core/hsgd.exchange`` and the ``comm_model.message_sizes``
    byte accounting (which bills θ0 as compressed). A previous version
    compressed only ζ1/ζ2 and transmitted θ0 dense, silently diverging from
    the eq. (19) bill on the LLM path.

    ``dp=True`` (a Python-level gate — the plain trace is unchanged) turns on
    the fused per-row L2-clip + Gaussian-noise stage inside the same kernel
    call; the step then takes traced ``dp_clip``/``dp_sigma`` scalars and a
    ``dp_key`` for the precomputed noise rows.
    """

    def exchange(params, batch, dp_clip=None, dp_sigma=None, dp_key=None):
        with jax.named_scope("exchange"):
            with jax.named_scope("intermediate"):
                z1 = model.h1(params["theta1"], batch["x1"])
                z2 = model.h2(params["theta2"], batch["x2"])
            msg = {"theta0": params["theta0"], "z1": z1, "z2": z2}
            if compression_k or quant or dp:
                from repro.kernels.compress import compress_pytree

                with jax.named_scope("compress"):
                    msg = compress_pytree(msg, compression_k or 1.0, quant,
                                          dp_clip=dp_clip if dp else None,
                                          dp_sigma=dp_sigma if dp else None,
                                          dp_key=dp_key if dp else None)
            return msg

    return exchange


def make_global_agg() -> Callable:
    """Eq. (2) over the leading group (pod) dim: mean + broadcast back.

    ``pod_weights`` (optional traced [G]) makes it the weighted eq. (2) —
    the pod-scale hook for the population layer's semi-async aggregation,
    where a late pod group's update is applied with a staleness-damped
    weight instead of blocking the round. None keeps the equal-weight mean,
    and since the weights are traced, varying them never recompiles.
    """

    def agg(params, pod_weights=None):
        with jax.named_scope("global_aggregation"):
            if pod_weights is None:
                def m(x):
                    g = jnp.mean(x.astype(jnp.float32), axis=0, keepdims=True).astype(x.dtype)
                    return jnp.broadcast_to(g, x.shape)

                return jax.tree.map(m, params)
            w = pod_weights.astype(jnp.float32)
            w = w / jnp.sum(w)

            def m(x):
                wb = w.reshape((-1,) + (1,) * (x.ndim - 1))
                g = jnp.sum(x.astype(jnp.float32) * wb, axis=0, keepdims=True).astype(x.dtype)
                return jnp.broadcast_to(g, x.shape)

            return jax.tree.map(m, params)

    return agg


# ---------------------------------------------------------------------------
# Plain (non-federated) steps
# ---------------------------------------------------------------------------


def make_plain_train_step(cfg: ModelConfig, lr: float = 1e-3, force_window=False) -> Callable:
    """Baseline sync-DP training step (beyond-paper comparison point)."""

    def step(params, batch):
        loss, grads = jax.value_and_grad(
            lambda p: T.lm_loss(cfg, p, batch, remat=True, force_window=force_window)
        )(params)
        new = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype), params, grads)
        return new, loss

    return step


def make_prefill_step(cfg: ModelConfig) -> Callable:
    def step(params, batch):
        hidden, _ = T.forward(
            cfg, params, batch["tokens"], extra_embeds=batch.get("extra_embeds"), remat=True
        )
        logits = T.logits_from_hidden(cfg, params, hidden[:, -1:])
        return logits

    return step


def make_decode_step(cfg: ModelConfig, force_window: bool = False) -> Callable:
    from repro.common.sharding import weight_mode

    def step(params, batch):
        index = jnp.asarray(batch_index_default(batch), jnp.int32)
        with weight_mode("fsdp"):  # decode: weights stay sharded (§Perf it. 2)
            logits, new_caches = T.decode_step(
                cfg, params, batch["tokens"], batch["caches"], index, force_window=force_window
            )
        return logits, new_caches

    return step


def batch_index_default(batch):
    """Decode write position: mid-cache (static for the dry-run)."""
    caches = batch["caches"]
    leaves = jax.tree_util.tree_leaves(caches)
    # cache length lives on axis 2 of stacked kv ([L, B, S, ...]) or ssm state
    for leaf in leaves:
        if leaf.ndim >= 3:
            return leaf.shape[2] // 2
    return 0


# ---------------------------------------------------------------------------
# Assembled program set per (arch, shape)
# ---------------------------------------------------------------------------


@dataclass
class Programs:
    """Callables + (input SDS, axes) per lowered program."""

    entries: Dict[str, Tuple[Callable, Tuple, Tuple]]  # name -> (fn, sds, axes)


def build_programs(cfg: ModelConfig, shape: InputShape, *, n_tower: int = 2,
                   multi_pod: bool = False) -> Programs:
    dt = _dtype(cfg)
    entries: Dict[str, Tuple[Callable, Tuple, Tuple]] = {}
    force_window = shape.name == "long_500k"

    if shape.kind == "train":
        model = make_hybrid(cfg, n_tower=n_tower)
        p_sds = {k: L.abstract_params(s, dt) for k, s in model.specs().items()}
        p_axes = {k: L.axes_tree(s) for k, s in model.specs().items()}
        b_sds, b_axes = hybrid_train_inputs(cfg, shape)
        if multi_pod:
            # per-group (per-pod) batch: global batch split across G groups
            b_sds = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((s.shape[0] // 2,) + s.shape[1:], s.dtype),
                b_sds, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
            )
        s_sds, s_axes = hybrid_stale_inputs(model, cfg, b_sds)

        step = make_hsgd_train_step(model)
        exch = make_exchange_step(model)
        agg = make_global_agg()

        if multi_pod:
            G = 2

            def stack(tree, axes_tree_, lead):
                sds = jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct((G,) + s.shape, s.dtype), tree,
                    is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
                )
                axes = jax.tree.map(
                    lambda a: (lead,) + tuple(a), axes_tree_,
                    is_leaf=lambda a: isinstance(a, tuple) and all(isinstance(x, (str, type(None))) for x in a),
                )
                return sds, axes

            p_sds, p_axes = stack(p_sds, p_axes, "pod_group")
            s_sds, s_axes = stack(s_sds, s_axes, "pod_group")
            b_sds, b_axes = stack(b_sds, b_axes, "pod_group")  # already per-group batch
            entries["train_step"] = (jax.vmap(step), (p_sds, s_sds, b_sds), (p_axes, s_axes, b_axes))
            entries["exchange"] = (jax.vmap(exch), (p_sds, b_sds), (p_axes, b_axes))
            entries["global_agg"] = (agg, (p_sds,), (p_axes,))
        else:
            entries["train_step"] = (step, (p_sds, s_sds, b_sds), (p_axes, s_axes, b_axes))
            entries["exchange"] = (exch, (p_sds, b_sds), (p_axes, b_axes))
            # single-pod global agg: degenerate (one group) — still lowered for
            # completeness with a leading dim of 1
            g_sds = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((1,) + s.shape, s.dtype), p_sds,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
            )
            g_axes = jax.tree.map(
                lambda a: (None,) + tuple(a), p_axes,
                is_leaf=lambda a: isinstance(a, tuple) and all(isinstance(x, (str, type(None))) for x in a),
            )
            entries["global_agg"] = (agg, (g_sds,), (g_axes,))
        return Programs(entries)

    # inference shapes: plain architecture
    p_sds = L.abstract_params(T.model_specs(cfg), dt)
    p_axes = L.axes_tree(T.model_specs(cfg))
    b_sds, b_axes = inference_inputs(cfg, shape, force_window)
    if shape.kind == "prefill":
        fn = make_prefill_step(cfg)
    else:
        fn = make_decode_step(cfg, force_window)
    if multi_pod:
        # inference scale-out across pods: batch sharded over pod too
        b_axes = jax.tree.map(
            lambda a: tuple(("pod_batch" if x == "batch" else x) for x in a), b_axes,
            is_leaf=lambda a: isinstance(a, tuple) and all(isinstance(x, (str, type(None))) for x in a),
        )
    entries["serve_step"] = (fn, (p_sds, b_sds), (p_axes, b_axes))
    return Programs(entries)


# ---------------------------------------------------------------------------
# LLM-scale compiled federated rounds
# ---------------------------------------------------------------------------


def init_llm_params(key, model: HybridModel, n_pods: int = 1, dtype=jnp.float32):
    """Alg. 1 line 1 at pod scale: every pod group starts from one global
    model. Leaves carry a leading [G] pod axis (G = 1 collapses to the
    single-group path at negligible vmap cost)."""
    params = model.init(key, dtype)
    return jax.tree.map(lambda x: jnp.stack([x] * n_pods), params)


def global_llm_params(params):
    """Collapse the pod axis to the observable global model (eq. (2), equal
    pod weights) — the flat {θ0, θ1, θ2} layout that checkpoints store and
    that ``model.h1/h2/loss`` and the serve-step specs consume."""
    return jax.tree.map(
        lambda x: jnp.mean(x.astype(jnp.float32), axis=0).astype(x.dtype),
        params)


@dataclass(frozen=True)
class LLMRoundRunner:
    """Compiled HSGD rounds over the ``llm_hybrid`` program set.

    One global round = [global_agg across pod groups] + Λ × [exchange +
    Q × hsgd_train_step], staged exactly like ``HSGDRunner._round_impl``:
    ``round_fn(P, Q, k, b)`` compiles ONE donating jitted scan executor per
    bucket (cached on the runner), η rides through as a traced scalar so the
    adaptive controller re-picks it for free, and the exchange compresses the
    whole {θ0, ζ1, ζ2} message in one fused ``compress_pytree`` call.

    Params carry a leading [G] pod axis (``init_llm_params``); per-round
    batches carry [Λ, G, ...] — one fresh token-stream batch per exchange
    interval per pod, so every exchange resamples instead of training on a
    frozen batch.

    The round names Algorithm 1's phases with the scopes of
    ``core/hsgd.PHASE_SCOPES`` (``local_step/hospital``,
    ``local_step/device``, ``exchange`` with ``intermediate`` and
    ``compress``, ``global_aggregation``), so a device trace splits its time
    by phase as it does the CNN round's. Scopes change metadata only.
    """

    model: HybridModel
    n_pods: int = 1
    n_shards: int = 2  # δ²-probe worker shards per pod (stats rounds)
    # (P, Q, k, b, collect) bucket -> compiled round executor
    _round_cache: Dict = field(default_factory=dict, compare=False, repr=False)

    def _round_impl(self, params, batches, eta, Q: int, lam: int,
                    compression_k: float, quant_levels: int, collect: bool,
                    pod_weights=None, dp_clip=None, dp_sigma=None, dp_key=None):
        model = self.model
        if self.n_pods > 1:
            # eq. (2) across pod groups; pod_weights = the population layer's
            # staleness-damped semi-async weights (None = synchronous mean)
            params = make_global_agg()(params, pod_weights)
        dp = dp_key is not None
        if dp:
            # per-interval, per-pod noise keys folded off the threaded round
            # key — deterministic, and fresh normals every exchange
            exch_dp = jax.vmap(
                make_exchange_step(model, compression_k, quant_levels, dp=True),
                in_axes=(0, 0, None, None, 0))
            ikeys = jax.vmap(lambda i: jax.random.fold_in(dp_key, i))(
                jnp.arange(lam))
            xs = (batches, ikeys)
            batch_of = lambda xs_i: xs_i[0]
            stale_of = lambda params, xs_i: exch_dp(
                params, xs_i[0], dp_clip, dp_sigma,
                jax.random.split(xs_i[1], self.n_pods))
        else:
            exch = jax.vmap(make_exchange_step(model, compression_k, quant_levels))
            xs = batches
            batch_of = lambda xs_i: xs_i
            stale_of = lambda params, xs_i: exch(params, xs_i)

        if not collect:
            step = jax.vmap(make_hsgd_train_step(model), in_axes=(0, 0, 0, None))

            def interval(params, xs_i):
                batch_i = batch_of(xs_i)
                stale = stale_of(params, xs_i)

                def sgd_step(params, _):
                    params, losses = step(params, stale, batch_i, eta)
                    return params, jnp.mean(losses)

                return jax.lax.scan(sgd_step, params, None, length=Q)

            params, losses = jax.lax.scan(interval, params, xs, length=lam)
            return params, losses.reshape(-1)

        stepf = jax.vmap(make_hsgd_step_stats(model, self.n_shards),
                         in_axes=(0, 0, 0, None))
        # template for the previous step's global-gradient proxy (fp32, one
        # model copy — the per-pod gbar mean)
        zeros_g = jax.tree.map(lambda x: jnp.zeros(x.shape[1:], jnp.float32), params)

        def interval(params, xs_i):
            batch_i = batch_of(xs_i)
            stale = stale_of(params, xs_i)

            def sgd_step(carry, _):
                params, prev_g, prev_ok = carry
                params, loss_pods, aux = stepf(params, stale, batch_i, eta)
                gbar = jax.tree.map(lambda x: jnp.mean(x, axis=0), aux["gbar"])
                # law of total variance: worker spread = within-pod shard
                # spread + pod-mean spread around the global mean
                pod_dev = jax.tree.map(
                    lambda x, m: jnp.sum((x - m[None]) ** 2,
                                         axis=tuple(range(1, x.ndim))),
                    aux["gbar"], gbar)
                delta2 = jnp.mean(aux["delta2"]) + jnp.mean(
                    sum(jax.tree_util.tree_leaves(pod_dev)))
                diff = tree_norm(tree_sub(gbar, prev_g))
                den = eta * tree_norm(prev_g)
                rho = jnp.where(prev_ok > 0.5, diff / jnp.maximum(den, 1e-12), 0.0)
                stats = {"loss": jnp.mean(loss_pods),
                         "gnorm2": tree_dot(gbar, gbar),
                         "delta2": delta2, "rho": rho, "rho_ok": prev_ok}
                return (params, gbar, jnp.ones((), jnp.float32)), stats

            (params, _, _), stats = jax.lax.scan(
                sgd_step, (params, zeros_g, jnp.zeros((), jnp.float32)),
                None, length=Q)
            return params, stats

        params, stats = jax.lax.scan(interval, params, xs, length=lam)
        stats = jax.tree.map(lambda x: x.reshape(-1), stats)  # [Λ, Q] -> [P]
        return params, stats

    def round_fn(self, P: int, Q: int, compression_k: float = 0.0,
                 quant_levels: int = 0, collect_stats: bool = True,
                 dp: bool = False):
        """Compiled single-round executor for a (P, Q, k, b) bucket.

        fn(params, batches, eta, pod_weights=None) -> (params, stats|losses).
        ``batches`` leaves lead with [Λ = P/Q, G, ...]; ``params`` is donated;
        ``eta`` and ``pod_weights`` (the semi-async staleness weights, when
        given) are traced. Cached per bucket — a run whose cadence varies
        round-to-round pays one compile per distinct bucket, not one per
        round.

        ``dp`` adds exactly one enable bit to the cache key; the executor then
        takes traced (dp_clip, dp_sigma, dp_key) after ``eta`` — re-picking σ
        or re-keying the round noise never recompiles (traced-η discipline).
        """
        if P < 1 or Q < 1 or P % Q:
            raise ValueError(f"P={P} must be a positive multiple of Q={Q}")
        key = (P, Q, compression_k, quant_levels, collect_stats)
        if dp:
            key = key + (True,)
        fn = self._round_cache.get(key)
        if fn is None:
            lam = P // Q

            if dp:
                # name keeps the llm_round prefix so compile_guard budgets
                # tracking r"llm_round" attribute this executor too
                @functools.partial(jax.jit, donate_argnums=(0,))
                def llm_round_dp(params, batches, eta, dp_clip, dp_sigma,
                                 dp_key, pod_weights=None):
                    return self._round_impl(params, batches, eta, Q, lam,
                                            compression_k, quant_levels,
                                            collect_stats, pod_weights,
                                            dp_clip=dp_clip, dp_sigma=dp_sigma,
                                            dp_key=dp_key)

                fn = self._round_cache[key] = llm_round_dp
                return fn

            # named so compile_guard can attribute compiles per executor
            @functools.partial(jax.jit, donate_argnums=(0,))
            def llm_round(params, batches, eta, pod_weights=None):
                return self._round_impl(params, batches, eta, Q, lam,
                                        compression_k, quant_levels,
                                        collect_stats, pod_weights)

            fn = self._round_cache[key] = llm_round
        return fn

    def run_fixed(self, params, batch_fn, steps: int, P: int, Q: int, lr: float,
                  compression_k: float = 0.0, quant_levels: int = 0):
        """Fixed-cadence driver (the pre-§VI baseline): exchange every Q,
        global agg every P, for ``steps / P`` whole compiled rounds.

        ``steps`` must be a positive multiple of P — rounds are compiled
        whole, and silently training more or fewer steps than asked would
        desynchronize trajectories, byte bills, and checkpoints (same
        no-silent-flooring rule as ``FederationConfig``). Callers with a free
        step budget round it themselves (see ``launch/train.py::run_llm``)."""
        if steps < P or steps % P:
            raise ValueError(
                f"steps={steps} must be a positive multiple of P={P} "
                f"(whole compiled rounds; round your budget explicitly)")
        fn = self.round_fn(P, Q, compression_k, quant_levels, collect_stats=False)
        losses = []
        for r in range(steps // P):
            params, l = fn(params, batch_fn(r, P // Q), lr)
            losses.append(np.asarray(jax.device_get(l)))
        return params, np.concatenate(losses)


class AdaptiveLLMRunner:
    """Closed-loop §VI controller over ``LLMRoundRunner`` — the same
    plan/probe/governor loop as ``core/controller.AdaptiveHSGDRunner``,
    rebased onto the LLM-scale state representation.

    * probes come from the LLM step's own gradients
      (``make_hsgd_step_stats``: δ² from per-shard/per-pod gradient spread,
      ‖∇F‖² from the pod-mean gradient, ρ from within-interval secants);
    * ``message_sizes`` is built from the ``llm_hybrid`` specs and the live
      ζ1/ζ2 token-stream shapes (``eval_shape`` on the actual batch);
    * the byte governor walks the same ``COMPRESSION_LADDER`` ratchet.
    """

    def __init__(self, model: HybridModel, cfg=None, n_pods: int = 1,
                 learning_rate: float = 1e-3, n_shards: int = 2):
        from repro.core.controller import AdaptiveConfig

        self.model = model
        self.cfg = cfg or AdaptiveConfig()
        self.n_pods = n_pods
        self.lr0 = learning_rate
        self.runner = LLMRoundRunner(model, n_pods=n_pods, n_shards=n_shards)
        # eq. (19) view of the pod topology: each pod group is one
        # hospital-device pair exchanging over the modeled links
        self.fed = FederationConfig(num_groups=n_pods, devices_per_group=1,
                                    alpha=1.0)

    def _sizes_of(self, params, batch):
        """``sizes_of(k, b)`` governor callback; ζ1/ζ2 element counts read off
        the live token-stream shapes via ``eval_shape`` (zero FLOPs)."""
        from repro.core import comm_model as CM

        pod_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), params)
        b_pod = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[2:], x.dtype), batch)
        z1 = jax.eval_shape(self.model.h1, pod_sds["theta1"], b_pod["x1"])
        z2 = jax.eval_shape(self.model.h2, pod_sds["theta2"], b_pod["x2"])
        z1_el, z2_el = tree_size(z1), tree_size(z2)

        def sizes_of(k_frac: float, levels: int):
            return CM.message_sizes(pod_sds, z1_el, z2_el,
                                    self.fed.sampled_devices, k_frac, levels)

        return sizes_of

    def _seed_probe(self, params, batches):
        """§VI-B pre-training probe, LLM-path flavour: two stats steps on one
        sampled stream (same batch ⇒ a clean ρ secant) yield the initial
        {ρ, δ, F0, ‖∇F‖²}. Compiled OUTSIDE the round cache and WITHOUT
        donation, so no training state is consumed and the one-executor-per-
        executed-bucket contract is untouched. ``cfg.probe_batch`` does not
        apply here — the probe batch is whatever ``batch_fn`` samples."""
        from repro.core.controller import probe_from_stats

        def llm_probe_round(p, b, eta):
            return self.runner._round_impl(p, b, eta, 2, 1, 0.0, 0, True)

        fn = jax.jit(llm_probe_round)
        _, stats = fn(params, batches, self.lr0)
        return probe_from_stats(jax.device_get(stats), Q=2)

    def run(self, params, batch_fn, probe=None):
        """Drive ``cfg.total_steps`` iterations adaptively.

        ``params`` is the pod-stacked pytree from ``init_llm_params`` (donated
        round-by-round — rebind the return value). ``batch_fn(round_idx, lam)``
        must return a fresh batch pytree with leading [Λ, G, ...] axes; it is
        called once per round plus once up front for shape inference and (with
        ``cfg.init_probe``) the seed probe, so it should be cheap and
        stateless-ish (a seeded sampler). Returns (params, per-step losses,
        per-round history).
        """
        from repro.core.controller import ControllerCore

        peek = batch_fn(0, 1)
        sizes_of = self._sizes_of(params, peek)
        if probe is None and self.cfg.init_probe:
            probe = self._seed_probe(params, peek)
        core = ControllerCore(self.cfg, self.fed, sizes_of, eta0=self.lr0,
                              probe=probe)
        losses = []
        while not core.done:
            plan, (k_frac, levels) = core.plan()
            batches = batch_fn(len(core.history), plan.P // plan.Q)
            fn = self.runner.round_fn(plan.P, plan.Q, k_frac, levels,
                                      collect_stats=True)
            params, stats = fn(params, batches, plan.eta)
            stats = jax.device_get(stats)
            losses.append(np.asarray(stats["loss"]))
            core.record(plan, stats)
        return params, np.concatenate(losses), core.history
