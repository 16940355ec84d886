"""The plain references agree with the program at small sizes on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import compress as RC
import hsgd_cnn as RCNN
import stablelm as RLM
import weights as W


def test_compress_reference_keeps_the_top_k_and_quantizes_them():
    from repro.core.compression import compress_rows_ref

    x = jax.random.normal(jax.random.PRNGKey(0), (64, 100), jnp.float32)
    k = RC.keep_count(100, 0.25)
    ref = np.asarray(RC.compress_rows(x, k, 128))
    prog = np.asarray(jax.jit(compress_rows_ref, static_argnames="levels")(x, k, levels=128))
    assert k == 25 and ((ref != 0).sum(axis=1) == 25).all()
    mag = np.abs(np.asarray(x))
    top = np.argsort(-mag, axis=1)[:, :25]
    assert all(set(np.flatnonzero(ref[i])) == set(top[i]) for i in range(64))
    # the program's threshold search keeps at least the exact top k
    both = (ref != 0) & (prog != 0)
    assert ((prog != 0) >= (ref != 0)).all()
    np.testing.assert_allclose(ref[both], prog[both], rtol=1e-6, atol=1e-6)


def test_cnn_towers_and_loss_match_the_program():
    from repro.models.split_model import cnn_hybrid

    model = cnn_hybrid()
    key = jax.random.PRNGKey(1)
    w = W.make(key, jax.eval_shape(model.init, key))
    x1 = jax.random.normal(key, (5, 11 * 28))
    x2 = jax.random.normal(jax.random.PRNGKey(2), (5, 17 * 28))
    y = jnp.arange(5) % 11
    with jax.default_matmul_precision("highest"):
        z1, z2 = RCNN.h1(w["theta1"], x1), RCNN.h2(w["theta2"], x2)
        np.testing.assert_allclose(z1, model.h1(w["theta1"], x1), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(z2, model.h2(w["theta2"], x2), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(RCNN.xent(RCNN.combined(w["theta0"], z1, z2), y),
                                   model.loss(w["theta0"], z1, z2, y), rtol=1e-6)


@pytest.fixture(scope="module")
def tiny_decoder():
    from repro.common.config import get_config

    return get_config("stablelm-1.6b").replace(
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, d_ff=128,
        vocab_size=97, tie_embeddings=False)


def test_decoder_logits_match_the_program(tiny_decoder):
    from repro.models import layers as L
    from repro.models import transformer as T

    cfg = tiny_decoder
    key = jax.random.PRNGKey(3)
    specs = T.model_specs(cfg)
    p = W.make(key, jax.eval_shape(lambda k: L.init_params(specs, k), key))
    toks = jax.random.randint(key, (1, 24), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        hidden, _ = T.forward(cfg, p, toks, remat=False)
        want = T.logits_from_hidden(cfg, p, hidden)[0]
        got = RLM.decoder_logits(p, toks[0], jnp.arange(24), cfg.rope_theta)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_llm_hybrid_loss_matches_the_program(tiny_decoder):
    from repro.models.split_model import llm_hybrid

    cfg = tiny_decoder
    model = llm_hybrid(cfg, n_tower=1, remat=False)
    key = jax.random.PRNGKey(4)
    w = W.make(key, jax.eval_shape(model.init, key))
    x1 = jax.random.randint(key, (2, 8), 0, cfg.vocab_size)
    x2 = jax.random.randint(jax.random.PRNGKey(5), (2, 8), 0, cfg.vocab_size)
    y = jax.random.randint(jax.random.PRNGKey(6), (2, 16), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        z1 = RLM.tower(w["theta1"], x1, cfg.rope_theta)
        z2 = RLM.tower(w["theta2"], x2, cfg.rope_theta)
        np.testing.assert_allclose(z1, model.h1(w["theta1"], x1), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(
            RLM.combined_loss(w["theta0"], z1, z2, y, cfg.rope_theta),
            model.loss(w["theta0"], z1, z2, y), rtol=1e-5)
