"""Device ms per decode step: mean duration of the runs of the engine's
jitted decode step (all slots, one token each) in the traced wave."""
LAYER = "serving scheduler"
UNIT = "ms"
BETTER = "lower"
MOVES = "serve_tokens_per_s"

from chipbench.readers import DECODE_PROGRAM, serve_runs  # noqa: E402


def read(record):
    runs = serve_runs(record, DECODE_PROGRAM)
    return 1e3 * sum(runs) / len(runs) if runs else None
