"""The e-health cell's check at a test's size: a sound run is correct, and
the run with its timed path broken underneath is not."""
import jax
import jax.numpy as jnp
import pytest

W = "ehealth-cnn-chsgd"


def test_sound_run_is_correct(tiny_run, system):
    run = tiny_run(W, seed=3)
    res = system(run).run(run, jax.devices())
    assert run.correct, run.checks
    assert res["attempted"] > 0 and res["end_to_end"]["train_step_ms"] > 0


def _unchanged(state, lr, g0, g1, g2):
    return state._replace(step=state.step + 1)


def _half_batch(orig):
    def gather(data, idx):
        h = idx.shape[1] // 2
        return orig(data, jnp.concatenate([idx[:, :h], idx[:, :h]], axis=1))
    return gather


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(tiny_run, system, monkeypatch, fault):
    from repro.core import federation as F
    from repro.core import hsgd

    if fault == "state_unchanged":
        monkeypatch.setattr(hsgd, "_apply_sgd", _unchanged)
    else:
        monkeypatch.setattr(F, "gather_batch", _half_batch(F.gather_batch))
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
