"""The e-health federation under test: compiled C-HSGD rounds of the paper's
CNN over a whole fleet (``core/hsgd.py::HSGDRunner.round_fn``).

Set-up builds the fleet's data with the program's own generator and 3-step
partition, makes the weights on the device from the seed, compiles the
round executor, and runs the first round through that same executor: its
losses and the global model's change are compared with the plain reference
(``bench/reference/hsgd_cnn.py``) once the window has closed. The window
drives the executor back to back, closed loop; each round draws its device
cohorts and samples inside the program.
"""
from __future__ import annotations

import sys

import numpy as np

import harness as H
import weights as W
from training import run_training

sys.path.insert(0, str(H.BENCH / "reference"))
import hsgd_cnn as REF  # noqa: E402


class Cell:
    executor = "hsgd_round"

    def __init__(self, run, devices):
        from repro.common.config import FederationConfig, TrainConfig
        from repro.models.split_model import cnn_hybrid

        self.config, tr = run.config, run.traffic
        m, f = self.config["model"], self.config["federation"]
        self.P, self.Q = tr["global_interval_P"], tr["local_interval_Q"]
        self.fed = FederationConfig(
            num_groups=f["num_groups"], devices_per_group=f["devices_per_group"],
            alpha=f["alpha"], local_interval=self.Q, global_interval=self.P,
            non_iid_labels_per_group=f["non_iid_labels_per_group"])
        self.train = TrainConfig(learning_rate=tr["learning_rate"],
                                 compression_k=tr["compression_k"],
                                 quantization_bits=tr["quant_levels"])
        self.model = cnn_hybrid(h_rows=m["hospital_rows"], width=m["image_cols"],
                                n_classes=m["n_classes"], embed_dim=m["embed_dim"])
        self.exe = self.glob = None

    def setup(self, seed: int):
        import jax
        import jax.numpy as jnp
        from repro.core.hsgd import make_group_weights
        from repro.data.partition import hybrid_partition
        from repro.data.synthetic import DATASETS, make_dataset

        key, np_seed = H.seed_parts(seed)
        self.k_w, self.k_run = jax.random.split(key)
        f = self.config["federation"]
        spec = DATASETS[self.config["dataset"]]
        X, y = make_dataset(spec, f["samples"], seed=np_seed)
        parts = hybrid_partition(spec, X, y, self.fed, seed=np_seed).stacked()
        self.data = {k: jnp.asarray(v) for k, v in parts.items()}
        self.gw = make_group_weights(self.data)
        self.lr = jnp.float32(self.train.learning_rate)
        self.shapes = jax.eval_shape(self.model.init, self.k_w)
        return self._state(W.make(self.k_w, self.shapes), self.k_run)

    def _state(self, weights, key):
        """The program's round state with the benchmark's weights and key."""
        import jax
        from repro.core import federation as F
        from repro.core.hsgd import init_state

        model, fed, data = self.model, self.fed, self.data

        @jax.jit
        def go(weights, key, data):
            st = init_state(key, model, fed, data)
            M, A = fed.num_groups, fed.sampled_devices
            t0 = F.broadcast_to_groups(weights["theta0"], M)
            t1 = F.broadcast_to_groups(weights["theta1"], M)
            t2 = F.broadcast_to_devices(F.broadcast_to_groups(weights["theta2"], M), A)
            stale = dict(st.stale, theta0=jax.tree.map(lambda x: x + 0, t0))
            return st._replace(theta0=t0, theta1=t1, theta2=t2, stale=stale, key=key)

        return go(weights, key, data)

    def compile(self, state):
        import jax
        from repro.core.hsgd import HSGDRunner, global_model

        if self.exe is None:
            runner = HSGDRunner(self.model, self.fed, self.train)
            self.exe = runner.round_fn(self.P, self.Q, collect_stats=False).lower(
                state, self.data, self.gw, self.lr).compile()
            self.glob = jax.jit(global_model).lower(state, self.gw).compile()

    def first_round(self, state):
        import jax
        import jax.numpy as jnp

        state, losses = self.exe(state, self.data, self.gw, self.lr)
        w0 = W.make(self.k_w, self.shapes)
        change = jax.tree.map(lambda a, b: jnp.linalg.norm(a - b),
                              self.glob(state, self.gw), w0)
        return state, np.asarray(losses), jax.device_get(change)

    def step(self, state, r):
        return self.exe(state, self.data, self.gw, self.lr)

    def reference(self, dtype, fault=None):
        return REF.round_readings(
            W.make(self.k_w, self.shapes), self.k_run, self.data, self.gw,
            self.fed.sampled_devices, self.P, self.Q, self.train.compression_k,
            self.train.quantization_bits, float(self.train.learning_rate),
            dtype=dtype, fault=fault)

    def facts(self, rounds, window_s):
        import counts

        return {
            "steps": rounds * self.P, "window_s": window_s,
            "exchanges": rounds * (self.P // self.Q),
            "model_flops_per_step": counts.cnn_fleet_flops_per_step(self.config, self.fed),
            "compress_mats": counts.hsgd_message_calls(
                self.shapes["theta0"], self.fed, self.config["model"]["embed_dim"]),
        }


def run(run, devices):
    return run_training(run, devices, Cell(run, devices))
