"""The LLM training cell's check at a test's size: a sound run is correct,
and the run with its timed path broken underneath is not."""
import jax
import jax.numpy as jnp
import pytest

W = "stablelm-d8-chsgd"


def test_sound_run_is_correct(tiny_run, system):
    run = tiny_run(W, seed=3)
    res = system(run).run(run, jax.devices())
    assert run.correct, run.checks
    assert res["attempted"] > 0


def _half_batch(orig):
    def grads(model, params, stale, batch):
        h = batch["y"].shape[0] // 2
        cut = lambda x: x[:h]
        stale = {"theta0": stale["theta0"], "z1": cut(stale["z1"]), "z2": cut(stale["z2"])}
        return orig(model, params, stale, jax.tree.map(cut, batch))
    return grads


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(tiny_run, system, monkeypatch, fault):
    from repro.launch import steps

    if fault == "state_unchanged":
        monkeypatch.setattr(steps, "_apply_update", lambda params, grads, lr: params)
    else:
        monkeypatch.setattr(steps, "hybrid_grads", _half_batch(steps.hybrid_grads))
    run = tiny_run(W, seed=4)
    system(run).run(run, jax.devices())
    assert not run.correct, run.checks


def test_control_fails_the_limits(tiny_run, system):
    """The reference in bfloat16, put in the program's place."""
    from training import readings

    run = tiny_run(W, seed=5)
    cell = system(run).Cell(run, jax.devices())
    cell.setup(5)
    ref = cell.reference(jnp.float32)
    low = cell.reference(jnp.bfloat16)
    got = readings(low[0], low[1], *ref)
    assert any(got[k] > run.limits[k] for k in got), got
