"""The program's ModelConfig for a dense decoder configuration file."""
from __future__ import annotations

import harness as H


def model_config(config):
    """Every size is taken from the file; the architecture from the
    program's registered config named by ``program_arch``."""
    from repro.common.config import get_config

    cfg = get_config(config["program_arch"]).replace(
        num_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        tie_embeddings=config["tie_word_embeddings"], dtype=config["torch_dtype"])
    if (cfg.family, cfg.mlp, cfg.norm, cfg.attention, cfg.sliding_window,
            cfg.qk_norm) != ("dense", "swiglu", "layernorm", "gqa", 0, False):
        raise H.BenchError(f"{config['program_arch']} is not the plain dense "
                           f"decoder the reference implements")
    return cfg
