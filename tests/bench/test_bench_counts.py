"""Work from shapes (bench/counts.py) and the peaks table, against hand
counts at small sizes."""
import json

import pytest

import counts
import harness as H
from repro.common.config import FederationConfig


def test_cnn_tower_flops_by_hand():
    # 4x4 image, one 3x3 conv 1->2 (2*16*9*1*2 = 576), pool to 2x2, linear 8->3 (48)
    assert counts.cnn_tower_flops(4, 4, channels=(2,), k=3, embed=3) == 576 + 48
    # the paper's device tower (17 x 28 rows): conv 16, pool 8x14, conv 32, pool 4x7
    want = 2 * 17 * 28 * 9 * 16 + 2 * 8 * 14 * 9 * 16 * 32 + 2 * 4 * 7 * 32 * 64
    assert counts.cnn_tower_flops(17, 28) == want


def test_cnn_fleet_step_by_hand():
    cfg = {"model": {"image_rows": 4, "image_cols": 4, "hospital_rows": 2,
                     "n_classes": 3, "conv_channels": [2], "conv_kernel": 3,
                     "embed_dim": 3, "combined_hidden": 5}}
    fed = FederationConfig(num_groups=2, devices_per_group=8, alpha=0.5,
                           local_interval=2, global_interval=4)
    f1 = 2 * 2 * 4 * 9 * 2 + 2 * 1 * 2 * 2 * 3      # 2x4 rows: conv 144, pool 1x2, linear 24
    f2 = f1
    fc = 2 * (6 * 5 + 5 * 3)
    per_sample = 3 * (f1 + fc) + 3 * f2 + 2 * fc + (f1 + f2) / 2
    assert counts.cnn_fleet_flops_per_step(cfg, fed) == pytest.approx(2 * 4 * per_sample)


def test_dense_layer_and_llm_step_by_hand():
    # d 4, 2 heads of 2, kv 2, ff 8, context 3
    proj = 2 * 4 * (2 + 4) * 2 + 2 * 2 * 2 * 4
    mlp = 2 * 3 * 4 * 8
    attn = 2 * 2 * 3 * 2 * 2
    assert counts.dense_layer_flops_per_token(4, 2, 2, 2, 8, 3) == proj + mlp + attn
    model = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 2,
             "intermediate_size": 8, "vocab_size": 10}
    B, S, L, n_t, Q = 2, 6, 3, 1, 2
    tower = B * 3 * counts.dense_layer_flops_per_token(4, 2, 2, 2, 8, 3)
    comb = B * S * (L * counts.dense_layer_flops_per_token(4, 2, 2, 2, 8, 6) + 2 * 4 * 10)
    want = 3 * (tower + comb) + 3 * tower + 2 * comb + 2 * tower / Q
    assert counts.llm_hybrid_flops_per_step(model, L, n_t, B, S, Q) == pytest.approx(want)
    assert counts.decoder_flops_per_token(model, L, 0) == L * (proj + mlp) + 2 * 4 * 10


def test_message_matrices_and_compress_bound():
    mats = counts.message_matrices([(3, 4), (2, 5, 4), (7, 11), (11,)])
    assert mats == [{"rows": 13, "width": 4}, {"rows": 8, "width": 11}]
    peaks = H.peaks_for("TPU v5 lite")
    least = counts.compress_least_seconds(mats, peaks)
    b = 2 * 4 * (13 * 4 + 8 * 11) + 2 * 4 * (13 + 8)
    assert least["bytes"] == b and least["ops"] == 8 * (13 * 4 + 8 * 11)
    assert least["bound"] == "hbm"
    assert least["seconds"] == pytest.approx(b / 819e9)


def test_peaks_table_is_keyed_by_device_kind_and_refuses_others():
    table = json.loads((H.BENCH / "peaks.json").read_text())
    assert "Google Cloud documentation, TPU v5e" in table["source"]
    v5e = H.peaks_for("TPU v5 lite")
    assert (v5e["bf16_flops"], v5e["hbm_bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(SystemExit):
        H.peaks_for("cpu")
