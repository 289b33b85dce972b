"""Share of the traced train window in which the device ran no op."""
LAYER = "device"
UNIT = "%"
BETTER = "lower"
MOVES = "train_tokens_per_s"

from chipbench.readers import trace, traced  # noqa: E402


def read(record):
    if not traced(record):
        return None
    lo, hi = record["window"]
    busy = trace.busy_s(record["events"], lo, hi)
    return 100.0 * (1.0 - busy / ((hi - lo) / 1e9))
