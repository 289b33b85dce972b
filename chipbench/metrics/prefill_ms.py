"""Device ms per admitted request of the prefill and the page commit
programs in the traced wave."""
LAYER = "serving scheduler"
UNIT = "ms"
BETTER = "lower"
MOVES = "serve_tokens_per_s"

from chipbench.readers import ADMIT_PROGRAMS, serve_runs  # noqa: E402


def read(record):
    prefills = serve_runs(record, ADMIT_PROGRAMS[0])
    if not prefills:
        return None
    total = sum(sum(serve_runs(record, p)) for p in ADMIT_PROGRAMS)
    return 1e3 * total / len(prefills)
