"""LLM-scale federated training under test: compiled llm_hybrid C-HSGD rounds
(``launch/steps.py::LLMRoundRunner.round_fn``) on a dense decoder config.

Set-up makes the weights on the device from the seed, compiles the round
executor, and runs the first round through it on the first batch from the
program's seeded token sampler (``data/synthetic.py::llm_batch_fn``): its
losses and the weights' change are compared with the plain reference
(``bench/reference/stablelm.py``) once the window has closed. The window
drives the executor back to back, each round on the sampler's next batch.
"""
from __future__ import annotations

import sys

import numpy as np

import harness as H
import weights as W
from decoder import model_config
from training import run_training

sys.path.insert(0, str(H.BENCH / "reference"))
import stablelm as REF  # noqa: E402


class Cell:
    executor = "llm_round"

    def __init__(self, run, devices):
        from repro.launch.steps import LLMRoundRunner
        from repro.models.split_model import llm_hybrid

        self.config, self.traffic = run.config, run.traffic
        tr, t = self.traffic, self.config["training"]
        self.P, self.Q = tr["global_interval_P"], tr["local_interval_Q"]
        self.k, self.b = tr["compression_k"], tr["quant_levels"]
        self.lr = float(tr["learning_rate"])
        self.cfg = model_config(self.config)
        self.model = llm_hybrid(self.cfg, n_tower=t["tower_layers"], remat=t["remat"])
        self.runner = LLMRoundRunner(self.model, n_pods=t["pods"])
        self.exe = None

    def weights(self):
        return W.make(self.key, self.shapes)

    def setup(self, seed: int):
        import jax
        import jax.numpy as jnp
        from repro.data.synthetic import llm_batch_fn

        self.key, np_seed = H.seed_parts(seed)
        self.shapes = jax.eval_shape(self.model.init, self.key)
        tr = self.traffic
        self.batch_fn = llm_batch_fn(self.cfg, tr["batch"], tr["seq"], n_pods=1,
                                     seed=np_seed)
        self.first = self.batch_fn(0, self.P // self.Q)
        self.eta = jnp.float32(self.lr)
        shapes = self.shapes
        with_pod = jax.jit(lambda k: jax.tree.map(lambda x: x[None], W.make(k, shapes)))
        return with_pod(self.key)

    def compile(self, params):
        if self.exe is None:
            self.exe = self.runner.round_fn(
                self.P, self.Q, self.k, self.b, collect_stats=False).lower(
                params, self.first, self.eta).compile()

    def first_round(self, params):
        import jax
        import jax.numpy as jnp

        params, losses = self.exe(params, self.first, self.eta)
        losses = np.asarray(losses)
        w0 = self.weights()
        change = jax.device_get(jax.tree.map(
            lambda a, b: jnp.linalg.norm(a[0] - b), params, w0))
        return params, losses, change

    def step(self, params, r):
        return self.exe(params, self.batch_fn(r, self.P // self.Q), self.eta)

    def reference(self, dtype, fault=None):
        import jax

        batches = [jax.tree.map(lambda x: x[i, 0], self.first)
                   for i in range(self.P // self.Q)]
        return REF.round_readings(self.weights, batches, self.cfg.rope_theta,
                                  self.P, self.Q, self.k, self.b, self.lr,
                                  dtype=dtype, fault=fault)

    def facts(self, rounds, window_s):
        import jax

        import counts

        conf, tr = self.config, self.traffic
        leaves = [tuple(s.shape) for s in jax.tree_util.tree_leaves(self.shapes["theta0"])]
        return {
            "steps": rounds * self.P, "window_s": window_s,
            "exchanges": rounds * (self.P // self.Q),
            "model_flops_per_step": counts.llm_hybrid_flops_per_step(
                conf, conf["training"]["combined_layers"],
                conf["training"]["tower_layers"], tr["batch"], tr["seq"], self.Q),
            "compress_mats": counts.message_matrices(
                leaves + [(tr["batch"], tr["seq"] // 2, self.cfg.d_model)] * 2),
        }


def run(run, devices):
    return run_training(run, devices, Cell(run, devices))
