"""The decode steps' share of the chip's roofline: the least time the
traced wave's decode steps could take (the larger of their operations
over the bf16 peak and their bytes over the HBM peak; weights as held
read once per step plus each slot's live K/V, counts.decode_step_*),
over the device time of those steps."""
LAYER = "decode model step"
UNIT = "%"
BETTER = "higher"
MOVES = "serve_tokens_per_s"

from chipbench.readers import DECODE_PROGRAM, counts, serve_runs  # noqa: E402


def read(record):
    runs = serve_runs(record, DECODE_PROGRAM)
    if not runs:
        return None
    cfg, peaks = record["config"], record["peaks"]
    kv_bits = record["traffic"]["kv"]["kv_bits"]
    waves = record["waves"]
    flops = waves * counts.decode_step_flops(
        cfg, record["decode_rows_per_wave"], record["live_tokens_per_wave"])
    weights = counts.decode_step_bytes(cfg, record["weight_bytes"], kv_bits,
                                       0.0)
    kv = counts.decode_step_bytes(cfg, 0, kv_bits, 1.0)
    nbytes = len(runs) * weights + waves * record["live_tokens_per_wave"] * kv
    least = max(flops / peaks["bf16_flops"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / sum(runs)
