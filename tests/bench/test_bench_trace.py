"""The trace reduction on a small recorded TPU trace: the e-health cell at
the training CLI's fleet (10 groups x 64 devices), one traced window of a
few compiled rounds on one TPU v5 lite."""
from pathlib import Path

import pytest

import harness as H

FIXTURE = Path(__file__).parent / "data" / "trace_fixture.xplane.pb"


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData

    TR = H.load_module(H.BENCH / "trace.py", "trace")
    return TR, TR.reduce(ProfileData.from_file(str(FIXTURE)), "bench_window")


def test_busy_and_idle_in_the_window(red):
    _, r = red
    assert r["chips"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6, abs=1e-9)


def test_the_compress_kernel_is_found_once_per_width_and_exchange(red):
    TR, r = red
    seconds, n = TR.op_seconds(r, TR.KERNELS["compress"])
    rounds = sum(1 for name, _ in r["modules"] if "hsgd_round" in name)
    # widths 11, 64 and 128; P / Q = 2 exchanges a round
    assert rounds > 0 and n == 3 * 2 * rounds
    assert 0 < seconds < r["busy_s"]


def test_enclosing_loops_count_as_busy_only(red):
    _, r = red
    assert not any(k.startswith("while") for k in r["ops"])
    assert sum(r["ops"].values()) <= r["busy_s"] * (1 + 1e-9)


def test_breakdown_holds_ten_of_each_at_most(red):
    TR, r = red
    b = TR.breakdown(r)
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert all(isinstance(name, str) and " = " not in name for name, _ in b["device_ops"])
