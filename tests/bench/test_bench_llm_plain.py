"""The cell ``stablelm-d8-plain`` at a test's size: ``LLMRoundRunner``'s
plain HSGD round (k 0, no quantization) on the published stablelm-2 decoder
against its plain reference (``bench/reference/stablelm2.py``) on seeded
random weights, through the
cell's own driver and check (``bench/training.py::readings``), and two
broken rounds that the check has to refuse.

Tolerances: the cell's own limits (``bench/limits/stablelm-d8-plain.json``).
On the CPU both sides run float32 matmuls in full precision, so a sound
round reads about 1e-6 on both numbers, far under them; a round that trains
on half of each batch moves the weights by a different gradient, and one
that leaves the state unchanged reads a ``change_gap`` of 1 by the measure's
own construction, both above them."""
import argparse
import time

import jax
import pytest

import harness as H
from conftest import TINY_DECODER
from test_bench_faults_llm import _half_batch

W = "stablelm-d8-plain"


def tiny_run(seed):
    """A harness Run of the cell with the decoder's widths, its batch (2) and
    its sequence (64) cut, P, Q, k, b and η as the cell has them."""
    files = H.cell_files(W)
    files["config"].update(TINY_DECODER)
    files["traffic"].update(batch=2, seq=64)
    args = argparse.Namespace(workload=W, seed=seed, seconds=1.0, trace=0)
    return H.Run(args, files, time.perf_counter())


def system(run):
    name = run.traffic["system"]
    return H.load_module(H.BENCH / "systems" / f"{name}.py", name)


def test_the_cell_runs_plain_hsgd():
    """The cell's mix turns compression off, so both sides run Algorithm 1
    without the "C": the exchange skips ``compress_pytree`` and the
    reference keeps whole rows, unquantized."""
    run = tiny_run(seed=1)
    cell = system(run).Cell(run, jax.devices())
    assert (cell.P, cell.Q, cell.k, cell.b) == (4, 2, 0.0, 0)
    assert cell.executor == "llm_round"


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_sound_round_is_correct(seed):
    run = tiny_run(seed)
    res = system(run).run(run, jax.devices())
    assert run.correct, run.checks
    assert res["attempted"] > 0
    got = {c["name"]: c["value"] for c in run.checks}
    assert set(got) == {"loss_gap", "change_gap"}
    # full-precision float32 on both sides: far inside the limits
    assert all(got[k] < 0.01 * run.limits[k] for k in got), got


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_round_is_not_correct(monkeypatch, fault):
    from repro.launch import steps

    if fault == "state_unchanged":
        monkeypatch.setattr(steps, "_apply_update", lambda params, grads, lr: params)
    else:
        monkeypatch.setattr(steps, "hybrid_grads", _half_batch(steps.hybrid_grads))
    run = tiny_run(seed=4)
    system(run).run(run, jax.devices())
    assert not run.correct, run.checks
    if fault == "state_unchanged":
        got = {c["name"]: c["value"] for c in run.checks}
        assert got["change_gap"] == pytest.approx(1.0)


@pytest.mark.parametrize("departure", ["full_rotary", "scaled_embeddings"])
def test_the_generic_decoder_is_not_correct(monkeypatch, departure):
    """The check tells the published decoder from the repo's generic one:
    a program that rotated the whole head or scaled the embeddings, as
    ``stablelm-1.6b`` does, is refused."""
    run = tiny_run(seed=5)
    sysmod = system(run)
    real = sysmod.model_config
    other = (dict(partial_rotary_factor=1.0) if departure == "full_rotary"
             else dict(embed_scale=True))
    monkeypatch.setattr(sysmod, "model_config", lambda c: real(c).replace(**other))
    sysmod.run(run, jax.devices())
    assert not run.correct, run.checks


def test_program_decoder_matches_the_reference():
    """The program's training forward of the published decoder, on the
    benchmark's seeded weights (q/k/v biases random, not zero), against the
    reference's logits; both in float32 at `highest` precision, so they
    differ by summation order alone."""
    import numpy as np

    import stablelm2
    import weights as Wt
    from repro.models import layers as L
    from repro.models import transformer as T

    run = tiny_run(seed=6)
    cfg = system(run).model_config(run.config)
    key = jax.random.PRNGKey(6)
    params = Wt.make(key, jax.eval_shape(lambda k: L.init_params(T.model_specs(cfg), k), key))
    assert float(np.abs(params["layers"]["attn"]["q_bias"]).max()) > 0.1
    tokens = jax.random.randint(jax.random.PRNGKey(7), (64,), 0, cfg.vocab_size)
    rot = int(cfg.resolved_head_dim * cfg.partial_rotary_factor)
    with jax.default_matmul_precision("highest"):
        h, _ = T.forward(cfg, params, tokens[None], remat=False)
        got = T.logits_from_hidden(cfg, params, h)[0]
        want = stablelm2.decoder_logits(params, tokens, cfg.rope_theta, rot)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_a_program_without_the_architecture_stops_cleanly(monkeypatch):
    """A checkout whose program has no ``stablelm-2-1.6b`` cannot run the
    cell: it stops before set-up, with a message and a non-zero exit."""
    from repro.common import config

    def unknown(name, smoke=False):
        raise KeyError(name)

    monkeypatch.setattr(config, "get_config", unknown)
    run = tiny_run(seed=1)
    with pytest.raises(SystemExit, match="no stablelm-2-1.6b architecture"):
        system(run).Cell(run, jax.devices())
