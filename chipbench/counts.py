"""Operations and bytes from a configuration's shapes and dtypes.

These count the work the algorithm needs, not what the current program
launches: padding, layout copies and recomputation do not count.
"""
from __future__ import annotations

import math


def _dims(cfg: dict):
    d, H, KV = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    return d, H, KV, cfg.get("head_dim") or d // H, cfg["d_ff"], \
        cfg["n_layers"], cfg["vocab_size"]


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix product per token: the
    attention and MLP projections of every layer, and the output head
    (the embedding lookup is a gather, not a product)."""
    d, H, KV, Dh, f, L, V = _dims(cfg)
    mlp = (3 if cfg.get("gated_mlp", True) else 2) * d * f
    per_layer = d * H * Dh + 2 * d * KV * Dh + H * Dh * d + mlp
    return L * per_layer + d * V


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward operations per token: 6 per matrix-product
    parameter, plus causal attention (QK^T and PV, each 2 S H Dh per token
    forward for a full row, halved for causality, tripled for backward)."""
    d, H, KV, Dh, f, L, V = _dims(cfg)
    return 6.0 * matmul_params(cfg) + 6.0 * L * H * Dh * seq_len


def quantized_elements(cfg: dict, min_quant_size: int = 4096) -> tuple:
    """(elements with quantized state, number of tensors among them):
    every parameter tensor of at least ``min_quant_size`` elements except
    the embedding's (the paper keeps its state in 32 bits)."""
    d, H, KV, Dh, f, L, V = _dims(cfg)
    mats = [d * H * Dh, d * KV * Dh, d * KV * Dh, H * Dh * d, d * f, f * d]
    if cfg.get("gated_mlp", True):
        mats.append(d * f)
    norm_parts = 2 if cfg.get("norm_type") == "layernorm" else 1
    tensors = [L * m for m in mats] + [L * d] * (2 * norm_parts)
    if not cfg.get("tie_embeddings", False):
        tensors.append(d * V)
    tensors = [n for n in tensors if n >= min_quant_size]
    return sum(tensors), tensors


def fused_update_bytes(cfg: dict, bits, block_size: int = 2048) -> float:
    """HBM bytes one fused 8-bit (or k-bit) Adam update must move over the
    quantized tensors: the f32 param read and written, the f32 grad read,
    each state slot's codes read and written at its width, and each
    block's f32 absmax read and written per slot.  0 for 32-bit state."""
    if bits == 32:
        return 0.0
    bits_m, bits_r = (bits, bits) if isinstance(bits, int) else bits
    _, tensors = quantized_elements(cfg)
    total = 0.0
    for n in tensors:
        blocks = math.ceil(n / block_size)
        total += n * (4 + 4 + 4)                       # p r/w, g r
        total += 2 * n * (bits_m + bits_r) / 8.0       # codes r/w
        total += 2 * 2 * blocks * 4                    # absmax r/w
    return total


def kv_bytes_per_token(cfg: dict, kv_bits: int) -> float:
    """Bytes of one token's K and V in the paged cache: codes at kv_bits
    per value and one f32 absmax per head row, over all layers."""
    d, H, KV, Dh, f, L, V = _dims(cfg)
    return L * 2 * KV * (Dh * kv_bits / 8.0 + 4)


def decode_step_bytes(cfg: dict, weight_bytes: int, kv_bits: int,
                      live_tokens: float) -> float:
    """Bytes a decode step must read: the weights of every matrix product
    as held (``weight_bytes`` per parameter) and the live K/V."""
    return matmul_params(cfg) * weight_bytes + \
        live_tokens * kv_bytes_per_token(cfg, kv_bits)


def decode_step_flops(cfg: dict, active: float, live_tokens: float) -> float:
    """Operations of a decode step: 2 per matrix-product parameter per
    active sequence, plus QK^T and PV over each sequence's live tokens."""
    d, H, KV, Dh, f, L, V = _dims(cfg)
    return 2.0 * matmul_params(cfg) * active + 4.0 * L * H * Dh * live_tokens
