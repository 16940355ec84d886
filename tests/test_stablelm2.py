"""The published stablelm-2 decoder (``stablelm-2-1.6b``): RoPE over a
quarter of each head, q/k/v biases and unscaled token embeddings, on the
training forward and on the cache-threading decode path; the generic
``stablelm-1.6b`` keeps its own equations."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import get_config
from repro.models import layers as L
from repro.models import transformer as T


def _random_params(cfg, seed=0):
    """Every leaf random (biases too), so that each term shows in the output."""
    params = L.init_params(T.model_specs(cfg), jax.random.PRNGKey(seed), jnp.float32)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(
        treedef, [x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])


def test_published_architecture():
    cfg = get_config("stablelm-2-1.6b")
    assert (cfg.partial_rotary_factor, cfg.qkv_bias, cfg.embed_scale,
            cfg.tie_embeddings) == (0.25, True, False, False)
    assert (cfg.d_model, cfg.num_heads, cfg.d_ff, cfg.vocab_size) == (2048, 32, 5632, 100352)
    old = get_config("stablelm-1.6b")
    assert (old.partial_rotary_factor, old.qkv_bias, old.embed_scale) == (1.0, False, True)
    specs = T.model_specs(cfg)["layers"]["attn"]
    assert specs["q_bias"].shape == (24, 32, 64)
    assert "q_bias" not in T.model_specs(old)["layers"]["attn"]


def test_partial_rope_rotates_only_the_leading_channels():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 7, 3, 16))
    pos = jnp.arange(7)[None]
    y = L.apply_partial_rope(x, pos, 10000.0, 4)
    np.testing.assert_array_equal(y[..., 4:], x[..., 4:])
    np.testing.assert_array_equal(y[..., :4], L.apply_rope(x[..., :4], pos, 10000.0))
    # frequencies come from the rotated width: channel pair (0, 2) turns by
    # position * 1, pair (1, 3) by position * 10000^(-1/2)
    ang = 3.0 * np.array([1.0, 0.01])
    want0 = x[0, 3, 0, 0] * np.cos(ang[0]) - x[0, 3, 0, 2] * np.sin(ang[0])
    want1 = x[0, 3, 0, 1] * np.cos(ang[1]) - x[0, 3, 0, 3] * np.sin(ang[1])
    np.testing.assert_allclose(y[0, 3, 0, :2], [want0, want1], rtol=1e-5)
    np.testing.assert_array_equal(L.apply_partial_rope(x, pos, 10000.0, 16),
                                  L.apply_rope(x, pos, 10000.0))


@pytest.mark.parametrize("change", ["full_rotary", "no_bias", "scaled_embeddings"])
def test_each_departure_changes_the_output(change):
    """Each of the three mechanisms acts: undoing one moves the logits."""
    cfg = get_config("stablelm-2-1.6b", smoke=True)
    params = _random_params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 12), 0, cfg.vocab_size)
    if change == "full_rotary":
        other = cfg.replace(partial_rotary_factor=1.0)
    elif change == "scaled_embeddings":
        other = cfg.replace(embed_scale=True)
    else:
        other = cfg
        params = jax.tree_util.tree_map_with_path(
            lambda p, x: jnp.zeros_like(x) if str(p[-1].key).endswith("_bias") else x, params)
    with jax.default_matmul_precision("highest"):
        h, _ = T.forward(cfg, _random_params(cfg), tokens, remat=False)
        h2, _ = T.forward(other, params, tokens, remat=False)
    # the final LayerNorm's output is of order one
    assert float(jnp.max(jnp.abs(h - h2))) > 1e-3


def test_decode_path_matches_the_training_forward():
    """Prefill of a prefix into the cache, then token by token: the logits
    at every position equal the training forward's (f32 cache)."""
    cfg = get_config("stablelm-2-1.6b", smoke=True)
    params = _random_params(cfg, seed=3)
    S, pre = 12, 5
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, S), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        h, _ = T.forward(cfg, params, tokens, remat=False)
        want = T.logits_from_hidden(cfg, params, h)
        caches = T.init_decode_caches(cfg, 1, 16, jnp.float32)
        got, caches = T.decode_step(cfg, params, tokens[:, :pre], caches, jnp.int32(0),
                                    fresh_cache=True)
        steps = [got]
        for i in range(pre, S):
            lg, caches = T.decode_step(cfg, params, tokens[:, i:i + 1], caches, jnp.int32(i))
            steps.append(lg)
    got = jnp.concatenate(steps, axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
