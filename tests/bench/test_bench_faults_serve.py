"""The serving cell's check at a test's size: a sound run is correct, and a
run whose tokens are altered where they are produced is not."""
import jax

W = "stablelm-d8-chat"


def test_sound_run_is_correct(tiny_run, system):
    run = tiny_run(W, seed=3, seconds=2.0)
    res = system(run).run(run, jax.devices())
    assert run.correct, run.checks
    assert res["failed"] == 0 and res["attempted"] > 0
    m = res["end_to_end"]
    assert m["ttft_p95_ms"] > 0 and m["tpot_p95_ms"] > 0 and m["serve_tokens_per_s"] > 0


def test_altered_token_is_not_correct(tiny_run, system, monkeypatch):
    from repro.launch import engine

    orig = engine.sample_token

    def altered(logits, key, temperature):
        return (orig(logits, key, temperature) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "sample_token", altered)
    run = tiny_run(W, seed=4, seconds=2.0)
    system(run).run(run, jax.devices())
    assert not run.correct, run.checks
