"""Device ms per decode step of the paged-KV gather kernel: its ops in
the trace (the Pallas call takes the name of the jitted
``paged_kv.gather_pages`` around it), summed over layers and K/V."""
LAYER = "kernels"
UNIT = "ms"
BETTER = "lower"
MOVES = "serve_tokens_per_s"

from chipbench.readers import DECODE_PROGRAM, serve_runs, trace  # noqa: E402

KERNEL = "gather_pages"


def read(record):
    runs = serve_runs(record, DECODE_PROGRAM)
    if not runs:
        return None
    lo, hi = record["window"]
    t = trace.op_time_s(record["events"], lo, hi,
                        lambda name: name.startswith(KERNEL))
    return 1e3 * t / len(runs) if t > 0 else None
