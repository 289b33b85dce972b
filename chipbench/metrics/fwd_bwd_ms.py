"""Device ms per train step of the ops under the step's
``tel.forward_backward`` scope (loss, forward and backward)."""
LAYER = "train step"
UNIT = "ms"
BETTER = "lower"
MOVES = "train_tokens_per_s"

from chipbench.readers import scope_ms_per_step  # noqa: E402


def read(record):
    return scope_ms_per_step(record, "tel.forward_backward")
