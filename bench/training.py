"""What the training cells share: the run of a cell (set-up, first round
checked, closed-loop window, reference), and the numbers compared.

A training system module defines ``Cell(run, devices)`` with

* ``setup(seed)``        -> the program's state, data and weights made from
  the seed (the executor compiles on the first call and is reused);
* ``compile(state)``     -> compile the round executor for the state;
* ``first_round(state)`` -> (state, losses, per-leaf norms of the change of
  the weights) after the first round through the window's own executor;
* ``step(state, r)``     -> (state, losses) of round r of the window;
* ``reference(dtype, fault=None)`` -> (losses, change norms, first-gradient
  norms) of the plain reference over the same first round, optionally with
  a planted fault (``half_batch``);
* ``facts(rounds, window_s)`` -> what the per-layer readers need;
* ``executor``, ``P``, ``Q``.
"""
from __future__ import annotations

import time

import numpy as np

import harness as H


def train_window(run, step, state, executor: str):
    """Drive ``state = step(state, r)`` back to back for ``run.seconds``.

    At most two rounds are in flight: after dispatching round r the host
    waits for round r-1's losses, so the loop never runs ahead of the chip
    by more than a round, and the window closes with ``block_until_ready``
    on the last state. Returns (state, rounds, window info)."""
    import jax

    rounds, pending = 0, None
    with run.window() as win:
        with run.span("bench_window"):
            t_end = time.perf_counter() + run.seconds
            while True:
                with run.span(f"dispatch {executor}"):
                    state, losses = step(state, rounds)
                rounds += 1
                if pending is not None:
                    with run.span("wait"):
                        pending.block_until_ready()
                pending = losses
                if time.perf_counter() >= t_end:
                    break
            with run.span("wait"):
                jax.block_until_ready(state)
    return state, rounds, win


def leaf_norms(tree):
    import jax

    return [float(np.asarray(x)) for x in jax.tree_util.tree_leaves(tree)]


def readings(losses_prog, change_prog, losses_ref, change_ref, gnorm_ref):
    """The compared numbers of a training cell.

    * ``loss_gap``: the worst relative gap between the program's and the
      reference's loss over the checked steps;
    * ``change_gap``: over the leaves, the worst gap between the norms of the
      program's and the reference's change of the weights, against that
      leaf's reference norm or the median leaf's, whichever is larger.
      Leaves whose first reference gradient is under a thousandth of the
      median leaf's move by round-off alone and are left out.
    """
    lp = np.asarray(losses_prog, np.float64)
    lr = np.asarray(losses_ref, np.float64)
    cp, cr = np.asarray(leaf_norms(change_prog)), np.asarray(leaf_norms(change_ref))
    g = np.asarray(leaf_norms(gnorm_ref))
    live = g >= 1e-3 * np.median(g)
    den = np.maximum(cr, np.median(cr[live]))
    return {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
            "change_gap": float(np.max((np.abs(cp - cr) / den)[live]))}


def run_training(run, devices, cell):
    """One run of a training cell: set-up, window, then the check."""
    with run.part("data and weights"):
        state = cell.setup(run.seed)
    with run.part(f"compile {cell.executor}"):
        cell.compile(state)
    with run.part("first round (checked)"):
        state, losses, change = cell.first_round(state)
    run.end_setup()

    state, rounds, win = train_window(run, cell.step, state, cell.executor)
    window_s, steps = win["window_s"], rounds * cell.P
    mem = H.memory_peak(devices)
    del state
    run.log("window", rounds=rounds, steps=steps, window_s=window_s)

    t = time.perf_counter()
    import jax.numpy as jnp

    losses_ref, change_ref, gnorm_ref = cell.reference(jnp.float32)
    run.log("reference", seconds=time.perf_counter() - t,
            losses=np.asarray(losses).tolist(),
            losses_ref=np.asarray(losses_ref).tolist())
    for name, value in readings(losses, change, losses_ref, change_ref,
                                gnorm_ref).items():
        run.check(name, value, run.limits[name])
    return {"end_to_end": {"train_step_ms": 1e3 * window_s / steps},
            "attempted": steps, "failed": 0, "memory_peak_bytes": mem,
            "facts": cell.facts(rounds, window_s), "trace_dir": win.get("trace_dir"),
            "window_span": "bench_window"}
