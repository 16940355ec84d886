"""LLM-scale federated training under test on the published stablelm-2
decoder: the compiled llm_hybrid HSGD rounds of ``llm_hsgd.py`` (set-up,
window and facts unchanged), with the program's ``stablelm-2-1.6b``
architecture (quarter-width rotary, q/k/v biases, unscaled embeddings) and
its first round checked against ``bench/reference/stablelm2.py``.

A program without that architecture cannot run the cell: the run stops
before set-up with a non-zero exit and no result line.
"""
from __future__ import annotations

import sys

import harness as H

sys.path.insert(0, str(H.BENCH / "reference"))
import stablelm2 as REF  # noqa: E402

BASE = H.load_module(H.BENCH / "systems" / "llm_hsgd.py", "llm_hsgd")


def model_config(config):
    """The program's ModelConfig for the configuration file: sizes from the
    file, the architecture from the program's registered ``program_arch``,
    which has to be the one the file publishes."""
    from repro.common.config import get_config

    try:
        cfg = get_config(config["program_arch"])
    except KeyError:
        raise H.BenchError(f"the program has no {config['program_arch']} "
                           f"architecture") from None
    cfg = cfg.replace(
        num_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        tie_embeddings=config["tie_word_embeddings"], dtype=config["parameter_dtype"])
    got = (cfg.family, cfg.mlp, cfg.norm, cfg.attention, cfg.sliding_window,
           cfg.qk_norm, cfg.partial_rotary_factor, cfg.qkv_bias, cfg.embed_scale)
    want = ("dense", "swiglu", "layernorm", "gqa", 0, False,
            config["partial_rotary_factor"], config["use_qkv_bias"], False)
    if got != want:
        raise H.BenchError(f"{config['program_arch']} is {got}, not the stablelm-2 "
                           f"decoder {want} that the reference implements")
    return cfg


class Cell(BASE.Cell):
    def __init__(self, run, devices):
        from repro.launch.steps import LLMRoundRunner
        from repro.models.split_model import llm_hybrid

        self.config, self.traffic = run.config, run.traffic
        tr, t = self.traffic, self.config["training"]
        self.P, self.Q = tr["global_interval_P"], tr["local_interval_Q"]
        self.k, self.b = tr["compression_k"], tr["quant_levels"]
        self.lr = float(tr["learning_rate"])
        self.cfg = model_config(self.config)
        c = self.config  # the reference's decoder comes from the file alone
        self.theta = float(c["rope_theta"])
        self.rot = int(c["hidden_size"] // c["num_attention_heads"] * c["partial_rotary_factor"])
        self.model = llm_hybrid(self.cfg, n_tower=t["tower_layers"], remat=t["remat"])
        self.runner = LLMRoundRunner(self.model, n_pods=t["pods"])
        self.exe = None

    def reference(self, dtype, fault=None):
        import jax

        batches = [jax.tree.map(lambda x: x[i, 0], self.first)
                   for i in range(self.P // self.Q)]
        return REF.round_readings(self.weights, batches, self.theta, self.rot,
                                  self.P, self.Q, self.k, self.b, self.lr,
                                  dtype=dtype, fault=fault)


def run(run, devices):
    return BASE.run_training(run, devices, Cell(run, devices))
