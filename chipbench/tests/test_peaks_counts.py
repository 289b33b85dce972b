"""Peaks by device kind, and operation/byte counts against hand counts."""
import json

import pytest

from chipbench import counts, peaks, registry


def test_v5e_peaks_come_from_the_table():
    p = peaks.lookup("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["int8_ops"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert p["ici_bits_per_s"] == 1600e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.lookup("TPU v9 imaginary")


def _cfg(name):
    return json.loads((registry.BENCH_DIR / "configs" / f"{name}.json")
                      .read_text())


def test_paper_lm_209m_counts():
    c = _cfg("paper-lm-209m")
    # per layer: q, k, v, o 1024x1024 each, MLP 1024x8192 in and out
    per_layer = 4 * 1024 * 1024 + 2 * 1024 * 8192
    head = 1024 * 50264
    assert counts.matmul_params(c) == 10 * per_layer + head == 261_185_536
    assert counts.train_flops_per_token(c, 512) == \
        6 * 261_185_536 + 6 * 10 * 16 * 64 * 512
    # quantized: every tensor but the embedding's; per-layer norms stack
    # to 10 x 1024 = 10240 elements (over the 4096 threshold)
    n_q, _ = counts.quantized_elements(c)
    assert n_q == 10 * per_layer + head + 4 * 10 * 1024 == 261_226_496
    blocks = sum(-(-n // 2048) for n in counts.quantized_elements(c)[1])
    assert counts.fused_update_bytes(c, 8) == 16 * n_q + 16 * blocks
    # a packed 4-bit first moment reads and writes half a byte less
    assert counts.fused_update_bytes(c, (4, 8)) == \
        counts.fused_update_bytes(c, 8) - n_q
    assert counts.fused_update_bytes(c, 32) == 0.0


def test_stablelm_1_6b_counts():
    c = _cfg("stablelm-1.6b")
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert counts.matmul_params(c) == 24 * per_layer + 2048 * 100352
    # f32 weights of the products read by one decode step: 5.75 GB
    assert counts.decode_step_bytes(c, 4, 8, 0) == \
        4 * counts.matmul_params(c)
    # one token of 8-bit K and V: 24 layers x 2 x 32 heads x (64 + 4) B
    assert counts.kv_bytes_per_token(c, 8) == 24 * 2 * 32 * 68
    assert counts.decode_step_bytes(c, 4, 8, 1000) == \
        4 * counts.matmul_params(c) + 1000 * 24 * 2 * 32 * 68
    assert counts.decode_step_flops(c, 16, 1000) == \
        2 * counts.matmul_params(c) * 16 + 4 * 24 * 32 * 64 * 1000
