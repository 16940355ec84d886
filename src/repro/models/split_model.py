"""The paper's hybrid decomposition θ = [θ0 (combined), θ1 (hospital), θ2 (device)]
as a uniform wrapper over every model family.

A ``HybridModel`` exposes exactly the objects Algorithm 1 manipulates:
  h1(θ1, X1) -> ζ1      hospital tower
  h2(θ2, X2) -> ζ2      device tower
  loss(θ0, ζ1, ζ2, y)   combined model + loss

Instantiations:
  * cnn_hybrid / lstm_hybrid — the paper's own e-health models, with the
    exact vertical feature split of §VII-A (image rows / time-series features).
  * llm_hybrid — the assigned LLM-scale architectures. The vertical partition
    is over the sequence: the hospital holds the clinical-record segment, the
    device holds the wearable-log segment (for VLM/audio, the hospital side is
    the modality-frontend embedding — its natural VFL role). Towers are
    ``n_tower`` family-consistent blocks; the combined model is the assigned
    architecture's full backbone + head.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.models import cnn as C
from repro.models import layers as L
from repro.models import lstm as R
from repro.models import transformer as T


@dataclass(frozen=True)
class HybridModel:
    name: str
    specs0: Any  # combined θ0
    specs1: Any  # hospital θ1
    specs2: Any  # device θ2
    h1: Callable  # (θ1, x1) -> ζ1
    h2: Callable  # (θ2, x2) -> ζ2
    loss: Callable  # (θ0, ζ1, ζ2, y) -> scalar
    predict: Callable  # (θ0, ζ1, ζ2) -> outputs
    # (θ2 [M, A, ...], x2 [M, A, ...], y [M, A], stale θ0 [M, ...], stale ζ1
    # [M, A, ...]) -> eq. (7)'s per-device g2 [M, A, ...], for a model whose
    # device tower has a faster batched form than vmap over batch-1 towers
    device_grads: Optional[Callable] = None

    def specs(self) -> Dict[str, Any]:
        return {"theta0": self.specs0, "theta1": self.specs1, "theta2": self.specs2}

    def init(self, key, dtype=jnp.float32):
        k0, k1, k2 = jax.random.split(key, 3)
        return {
            "theta0": L.init_params(self.specs0, k0, dtype),
            "theta1": L.init_params(self.specs1, k1, dtype),
            "theta2": L.init_params(self.specs2, k2, dtype),
        }

    def full_loss(self, params, x1, x2, y):
        """Centralized view: fresh towers + combined (used by baselines/tests)."""
        z1 = self.h1(params["theta1"], x1)
        z2 = self.h2(params["theta2"], x2)
        return self.loss(params["theta0"], z1, z2, y)


# ---------------------------------------------------------------------------
# Paper models
# ---------------------------------------------------------------------------


def cnn_hybrid(
    h_rows: int = 11,
    width: int = 28,
    n_classes: int = 11,
    embed_dim: int = 64,
) -> HybridModel:
    """OrganAMNIST: hospital holds top h_rows rows (≈300px), device the rest."""
    d_rows = width - h_rows

    def h1(t, x1):
        return C.tower_forward(t, x1, h_rows, width)

    def h2(t, x2):
        return C.tower_forward(t, x2, d_rows, width)

    def predict(t0, z1, z2):
        return C.combined_forward(t0, z1, z2)

    def loss(t0, z1, z2, y):
        return C.classification_loss(predict(t0, z1, z2), y)

    def device_loss(proj, feats_n, y_n, t0_m, z1_n):
        return loss(t0_m, z1_n[None], L.dense(proj, feats_n[None]), y_n[None])

    def device_grads(t2, x2, y, t0, z1):
        """Each device's θ2 owns its filters, so the conv stack runs for a
        group's A devices at once with the device axis on the lanes (vmapped
        over the M groups); the proj leaf and the combined head stay
        per-device under vmap as in ``_device_loss``. θ2_n reaches only
        device n's loss, so the gradient of their sum is every device's own
        g2."""
        def features(convs, x2_m):  # one group's A devices on the lanes
            return C.tower_features_lanes(convs, x2_m, d_rows, width)

        def total(t2):
            convs = {k: v for k, v in t2.items() if k != "proj"}
            with jax.named_scope("conv"):
                feats = jax.vmap(features)(convs, x2)
            per_device = jax.vmap(device_loss, in_axes=(0, 0, 0, None, 0))
            return jnp.sum(jax.vmap(per_device)(t2["proj"], feats, y, t0, z1))

        return jax.grad(total)(t2)

    return HybridModel(
        name="paper_cnn",
        specs0=C.combined_specs(embed_dim, n_classes),
        specs1=C.tower_specs(h_rows, width, embed_dim=embed_dim),
        specs2=C.tower_specs(d_rows, width, embed_dim=embed_dim),
        h1=h1,
        h2=h2,
        loss=loss,
        predict=predict,
        device_grads=device_grads,
    )


def lstm_hybrid(
    n_features: int = 76,
    hospital_features: int = 36,
    n_classes: int = 2,
    d_hidden: int = 64,
    embed_dim: int = 64,
) -> HybridModel:
    """MIMIC-III / ESR: per-timestep feature split (36/40 for MIMIC)."""
    dev_features = n_features - hospital_features

    def h1(t, x1):
        return R.tower_forward(t, x1)

    def h2(t, x2):
        return R.tower_forward(t, x2)

    def predict(t0, z1, z2):
        return C.combined_forward(t0, z1, z2)

    def loss(t0, z1, z2, y):
        return C.classification_loss(predict(t0, z1, z2), y)

    return HybridModel(
        name="paper_lstm",
        specs0=C.combined_specs(embed_dim, n_classes),
        specs1=R.tower_specs(hospital_features, d_hidden, embed_dim),
        specs2=R.tower_specs(dev_features, d_hidden, embed_dim),
        h1=h1,
        h2=h2,
        loss=loss,
        predict=predict,
    )


# ---------------------------------------------------------------------------
# LLM-scale hybrid (assigned architectures)
# ---------------------------------------------------------------------------


def _tower_cfg(cfg: ModelConfig, n_tower: int) -> ModelConfig:
    """Family-consistent tower blocks at full width, shallow depth."""
    kw = dict(num_layers=n_tower, first_dense_layers=0, num_experts=0,
              experts_per_token=0, num_shared_experts=0)
    if cfg.family in ("ssm", "hybrid"):
        return cfg.replace(family="ssm", **kw)
    if cfg.d_ff == 0:  # attention-free cfg needs an ff for dense tower blocks
        kw["d_ff"] = 4 * cfg.d_model
    return cfg.replace(family="dense", attention=cfg.attention,
                       hybrid_attn_every=0, **kw)


def _tower_stack_specs(cfg: ModelConfig, n_tower: int, with_embed: bool):
    tcfg = _tower_cfg(cfg, n_tower)
    kind = "mamba" if tcfg.family == "ssm" else "attn_mlp"
    s = {"layers": T.stack_specs(tcfg, n_tower, kind), "norm": L.norm_specs(cfg.norm, cfg.d_model)}
    if with_embed:
        s["embed"] = L.embed_specs(cfg.vocab_size, cfg.d_model)
    return s, tcfg


def _tower_forward(tcfg: ModelConfig, params, x_or_tokens, remat=True):
    if "embed" in params:
        x = L.embed(params["embed"], x_or_tokens)
        if tcfg.embed_scale:
            x = x * jnp.asarray(jnp.sqrt(jnp.float32(tcfg.d_model)), x.dtype)
    else:
        x = x_or_tokens
    x, _ = T.backbone_forward(tcfg, {"layers": params["layers"]}, x, remat=remat)
    return L.apply_norm(tcfg.norm, params["norm"], x)


def llm_hybrid(cfg: ModelConfig, n_tower: int = 2, remat: bool = True) -> HybridModel:
    """Wrap an assigned architecture into the paper's hybrid decomposition."""
    modality = cfg.family in ("audio", "vlm")
    # hospital tower: modality embeddings for audio/vlm, token segment otherwise
    s1, tcfg1 = _tower_stack_specs(cfg, n_tower, with_embed=not modality)
    s2, tcfg2 = _tower_stack_specs(cfg, n_tower, with_embed=True)

    specs0 = T.model_specs(cfg)
    del specs0["embed"]  # combined model consumes ζ, not tokens
    specs0["head"] = L.dense_specs(cfg.d_model, cfg.vocab_size, (None, "vocab"), scale=0.02)

    def h1(t1, x1):
        return _tower_forward(tcfg1, t1, x1, remat)

    def h2(t2, x2):
        return _tower_forward(tcfg2, t2, x2, remat)

    def hidden_fn(t0, z1, z2):
        if cfg.family == "audio":
            x = T.audio_forward(t0, z2, z1, None, cfg, remat)
        else:
            x = jnp.concatenate([z1.astype(z2.dtype), z2], axis=1)
            x, _ = T.backbone_forward(cfg, t0, x, remat=remat)
        return L.apply_norm(cfg.norm, t0["final_norm"], x)

    def predict(t0, z1, z2):
        return L.dense(t0["head"], hidden_fn(t0, z1, z2))

    def loss(t0, z1, z2, y):
        hidden = hidden_fn(t0, z1, z2)
        # labels cover the token region (device segment + hospital segment for
        # text-text splits; decoder tokens for enc-dec/vlm)
        Sy = y.shape[1]
        hidden = hidden[:, -Sy:]
        # fused chunked head+CE — full logits never materialize (§Perf it. 6)
        head_cfg = cfg.replace(tie_embeddings=False)
        return T.chunked_lm_head_loss(head_cfg, t0, hidden, y, remat)

    return HybridModel(
        name=f"hybrid_{cfg.name}",
        specs0=specs0,
        specs1=s1,
        specs2=s2,
        h1=h1,
        h2=h2,
        loss=loss,
        predict=predict,
    )
