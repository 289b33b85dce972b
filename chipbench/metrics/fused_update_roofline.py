"""The fused 8-bit update kernel's share of its roofline: the HBM bytes
the update must move (counts.fused_update_bytes: params, grads, codes and
absmax of the quantized state) over the HBM peak, divided by the device
time of the kernel's ops (``fused_update_pallas``) per step.  The update
is bandwidth-bound: a few operations per byte."""
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "train_tokens_per_s"

from chipbench.readers import counts, steps, trace, traced  # noqa: E402

KERNEL = "fused_update_pallas"


def read(record):
    if not traced(record):
        return None
    bits = 8 if record["traffic"]["optimizer"].endswith("8") else 32
    need = counts.fused_update_bytes(record["config"], bits)
    n = steps(record)
    lo, hi = record["window"]
    t = trace.op_time_s(record["events"], lo, hi,
                        lambda name: name.startswith(KERNEL))
    if need <= 0 or n == 0 or t <= 0:
        return None
    return 100.0 * need / record["peaks"]["hbm_bytes_per_s"] / (t / n)
