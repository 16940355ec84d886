"""stablelm-1.6b [dense] — 24L d=2048 32H (GQA kv=32) ff=5632 V=100352.
[hf:stabilityai/stablelm-2-1_6b]

``stablelm-1.6b`` is the repo's generic dense decoder at these widths: RoPE
over the whole head, no q/k/v biases, embeddings scaled by sqrt(d_model).
``stablelm-2-1.6b`` is the published architecture: RoPE over the first
quarter of each head (``partial_rotary_factor`` 0.25), biases on the q, k and
v projections (``use_qkv_bias``), embeddings unscaled."""
from repro.common.config import ModelConfig, register_config


def full() -> ModelConfig:
    return ModelConfig(
        name="stablelm-1.6b", family="dense", num_layers=24, d_model=2048,
        num_heads=32, num_kv_heads=32, d_ff=5632, vocab_size=100352,
        mlp="swiglu", norm="layernorm", rope_theta=10000.0,
        source="hf:stabilityai/stablelm-2-1_6b",
    )


def smoke() -> ModelConfig:
    return full().replace(num_layers=2, d_model=128, num_heads=8, num_kv_heads=8,
                          d_ff=256, vocab_size=512)


# what stablelm-2-1_6b's config.json sets apart from ``full()``
STABLELM2 = dict(name="stablelm-2-1.6b", partial_rotary_factor=0.25, qkv_bias=True,
                 embed_scale=False, tie_embeddings=False, max_seq_len=4096)


def published() -> ModelConfig:
    return full().replace(**STABLELM2)


def published_smoke() -> ModelConfig:
    return smoke().replace(**STABLELM2)


register_config("stablelm-1.6b", full, smoke)
register_config("stablelm-2-1.6b", published, published_smoke)
