"""Device ms per train step of the ops under the step's
``tel.optimizer_update`` scope (the optimizer's whole update)."""
LAYER = "optimizer engine"
UNIT = "ms"
BETTER = "lower"
MOVES = "train_tokens_per_s"

from chipbench.readers import scope_ms_per_step  # noqa: E402


def read(record):
    return scope_ms_per_step(record, "tel.optimizer_update")
