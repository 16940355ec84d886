"""Work computed from shapes, the same for every implementation.

Model FLOPs count each multiply-add as 2 and only the matrix products and
convolutions the algorithm needs: recomputation (remat) is not counted, and
neither are norms, activations or softmax. A backward pass costs twice its
forward where the weights get gradients (input and weight gradients), and
once where only the input gradient flows on (the device's pass back through
a frozen combined model). Attention counts its two products over the whole
S x S score matrix (the PaLM convention), without halving for the mask.

The compress kernel's least work is one read and one write of each f32
message entry (plus a keep count and a row length per row) and 8 operations
per entry (magnitude, compare, select, and the five of the quantizer);
at 1 operation per byte it is bound by HBM bandwidth on every chip in
``peaks.json``.
"""
from __future__ import annotations

import math
from typing import Dict, List

COMPRESS_OPS_PER_ENTRY = 8


# -- the paper's CNN -------------------------------------------------------


def cnn_tower_flops(rows: int, cols: int, channels=(16, 32), k: int = 3,
                    embed: int = 64) -> int:
    """Forward FLOPs of one tower on one sample: SAME 3x3 convs, each
    followed by a 2x2/2 max-pool (floor), then the linear map to ``embed``."""
    total, c_in = 0, 1
    for c in channels:
        total += 2 * rows * cols * k * k * c_in * c
        rows, cols, c_in = rows // 2, cols // 2, c
    return total + 2 * rows * cols * c_in * embed


def cnn_combined_flops(embed: int = 64, hidden: int = 128, classes: int = 11) -> int:
    return 2 * (2 * embed * hidden + hidden * classes)


def cnn_fleet_flops_per_step(config: Dict, fed) -> float:
    """Model FLOPs of one HSGD step of the whole fleet, with the exchange
    (forward of both towers on the A_m samples) amortised over Q steps."""
    m = config["model"]
    rows, cols, h = m["image_rows"], m["image_cols"], m["hospital_rows"]
    ch, k, e = tuple(m["conv_channels"]), m["conv_kernel"], m["embed_dim"]
    f1 = cnn_tower_flops(h, cols, ch, k, e)
    f2 = cnn_tower_flops(rows - h, cols, ch, k, e)
    fc = cnn_combined_flops(e, m["combined_hidden"], m["n_classes"])
    samples = fed.num_groups * fed.sampled_devices
    hospital = 3 * (f1 + fc)
    device = 3 * f2 + 2 * fc
    exchange = (f1 + f2) / fed.local_interval
    return float(samples * (hospital + device + exchange))


# -- a dense decoder (llm_hybrid and serving) --------------------------------


def dense_layer_flops_per_token(d: int, heads: int, kv_heads: int, head_dim: int,
                                d_ff: int, context: int, gated: bool = True) -> int:
    proj = 2 * d * (heads + 2 * kv_heads) * head_dim + 2 * heads * head_dim * d
    mlp = 2 * (3 if gated else 2) * d * d_ff
    attn = 2 * 2 * context * heads * head_dim
    return proj + mlp + attn


def llm_hybrid_flops_per_step(model: Dict, n_layers: int, n_tower: int,
                              batch: int, seq: int, Q: int) -> float:
    """Model FLOPs of one llm_hybrid HSGD step: each half of the sequence
    goes through its tower (n_tower layers at seq/2), the joined sequence
    through the n_layers combined layers and the vocabulary head. Hospital:
    forward and backward of tower 1 and the combined model; device: forward
    and backward of tower 2 plus forward and input-gradient of the combined
    model; the exchange (both towers forward) every Q steps."""
    d, h, kv = model["hidden_size"], model["num_attention_heads"], model["num_key_value_heads"]
    hd, ff, V = d // h, model["intermediate_size"], model["vocab_size"]
    half = seq // 2
    tower = batch * half * n_tower * dense_layer_flops_per_token(d, h, kv, hd, ff, half)
    comb = batch * seq * (n_layers * dense_layer_flops_per_token(d, h, kv, hd, ff, seq)
                          + 2 * d * V)
    hospital = 3 * (tower + comb)
    device = 3 * tower + 2 * comb
    exchange = 2 * tower / Q
    return float(hospital + device + exchange)


def decoder_flops_per_token(model: Dict, n_layers: int, context: int) -> float:
    """Forward FLOPs of one token of the dense decoder at a given context:
    every layer and the vocabulary head (serving)."""
    d, h, kv = model["hidden_size"], model["num_attention_heads"], model["num_key_value_heads"]
    hd, ff, V = d // h, model["intermediate_size"], model["vocab_size"]
    return float(n_layers * dense_layer_flops_per_token(d, h, kv, hd, ff, context)
                 + 2 * d * V)


# -- the compress kernel --------------------------------------------------------


def message_matrices(leaf_shapes: List[tuple]) -> List[Dict[str, int]]:
    """The [rows, width] matrices a message is compressed as: each leaf read
    as rows of its last axis, leaves of one width stacked into one matrix."""
    by_width: Dict[int, int] = {}
    for s in leaf_shapes:
        n = int(s[-1]) if len(s) else 1
        by_width[n] = by_width.get(n, 0) + int(math.prod(s)) // n
    return [{"rows": r, "width": n} for n, r in sorted(by_width.items())]


def compress_bytes(rows: int, width: int) -> int:
    return 2 * 4 * rows * width + 2 * 4 * rows


def compress_ops(rows: int, width: int) -> int:
    return COMPRESS_OPS_PER_ENTRY * rows * width


def compress_least_seconds(mats: List[Dict[str, int]], peaks: Dict) -> Dict[str, float]:
    """Least time of compressing these matrices on a chip, and which bound."""
    b = sum(compress_bytes(m["rows"], m["width"]) for m in mats)
    o = sum(compress_ops(m["rows"], m["width"]) for m in mats)
    t_mem, t_ops = b / peaks["hbm_bytes_per_s"], o / peaks["bf16_flops"]
    return {"seconds": max(t_mem, t_ops), "bound": "hbm" if t_mem >= t_ops else "ops",
            "bytes": b, "ops": o}


def hsgd_message_calls(theta0_shapes, fed, embed_dim: int) -> List[Dict[str, int]]:
    """The e-health exchange message: the [M, ...] theta0 snapshot and the
    [M, A, embed] zeta1 and zeta2."""
    import jax

    M, A = fed.num_groups, fed.sampled_devices
    shapes = [(M,) + tuple(s.shape) for s in jax.tree_util.tree_leaves(theta0_shapes)]
    shapes += [(M, A, embed_dim)] * 2
    return message_matrices(shapes)
