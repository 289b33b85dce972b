"""Whole train step's share of the chip's bf16 peak: model operations per
token (counts.train_flops_per_token) times the tokens of the traced
window's steps, over the traced window, chips and peak."""
LAYER = "train step"
UNIT = "%"
BETTER = "higher"
MOVES = "train_tokens_per_s"

from chipbench.readers import counts, steps, traced  # noqa: E402


def read(record):
    if not traced(record):
        return None
    n = steps(record)
    lo, hi = record["window"]
    flops = n * record["tokens_per_step"] * counts.train_flops_per_token(
        record["config"], record["traffic"]["seq_len"])
    if n == 0:
        return None
    peak = record["chips"] * record["peaks"]["bf16_flops"]
    return 100.0 * flops / ((hi - lo) / 1e9) / peak
