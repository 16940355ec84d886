"""The open-loop generator: fixed work per seed, and requests timed from
when they were due."""
import time

import numpy as np
import pytest

import loadgen

MIX = {"rate_per_s": 5.0,
       "prompt_len": {"median": 256, "sigma": 1.0, "min": 16, "max": 1536},
       "output_len": {"median": 96, "sigma": 0.8, "min": 8, "max": 384}}


def test_every_seed_gets_the_same_work_in_another_order():
    a = loadgen.arrivals(MIX, 1, 30.0, 1000)
    b = loadgen.arrivals(MIX, 2 ** 31 + 12345, 30.0, 1000)
    assert len(a) == len(b) == 150
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    assert a[0].due_s == b[0].due_s == 0.0
    assert np.mean(np.diff([x.due_s for x in a])) == pytest.approx(0.2, rel=0.1)
    assert len(set(len(x.prompt) for x in a)) == 150  # distinct prompt lengths
    assert all(16 <= len(x.prompt) <= 1536 and 8 <= x.max_new <= 384 for x in a)


def test_lengths_follow_the_truncated_lognormal():
    xs = loadgen.lognormal_lengths(1001, 256, 1.0, 16, 1536)
    assert xs[500] == pytest.approx(256, rel=0.05)
    assert min(xs) >= 16 and max(xs) <= 1536
    assert loadgen.distinct([5, 5, 5, 6], 1, 10) == [5, 6, 4, 7]


class StallingEngine:
    """Stands in for ServeEngine: every step takes ``stall`` seconds and
    finishes whatever was admitted."""

    def __init__(self, stall):
        from repro.launch.engine import Request

        self.Request, self.stall = Request, stall
        self.waiting, self.done, self._slots, self._next = [], [], [], 0

    def submit(self, prompt, max_new):
        self.waiting.append(self.Request(self._next, prompt, max_new,
                                         t_submit=time.perf_counter()))
        self._next += 1
        return self._next - 1

    def pending(self):
        return len(self.waiting)

    def step(self):
        t = time.perf_counter()
        time.sleep(self.stall)
        for r in self.waiting:
            r.t_admit, r.t_first = t, time.perf_counter()
            r.tokens = [1] * r.max_new
            r.t_done = r.t_first
            self.done.append(r)
        self.waiting = []


def test_requests_are_timed_from_their_due_time(tiny_run, system):
    """A request due during a stall of the engine counts the stall: its time
    to first token is measured from when it was due, not from submission."""
    import jax

    run = tiny_run("stablelm-d8-chat", seconds=1.0)
    cell = system(run).Cell(run, jax.devices())
    cell.arrivals = [loadgen.Arrival(t, np.zeros(4, np.int32), 2)
                     for t in (0.0, 0.05, 0.1, 0.15)]
    cell.engine = StallingEngine(stall=0.5)
    reqs, due, w = cell.window(run, 1.0)
    by_due = sorted(reqs, key=lambda r: due[r.rid])
    # the three requests due while the first step stalled waited for it
    for r in by_due[1:]:
        assert r.t_first - due[r.rid] >= 0.5 - (due[r.rid] - due[by_due[0].rid]) - 1e-3
        assert r.t_first - due[r.rid] > r.t_first - r.t_submit
    m = cell.metrics(reqs, due, w)
    assert m["ttft_p95_ms"] >= 0.5 * 1e3 - 160
