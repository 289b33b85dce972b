"""Device ms per train step of the ops under the model's ``tel.attention``
scopes (every layer's attention, forward, recomputation and backward)."""
LAYER = "train step"
UNIT = "ms"
BETTER = "lower"
MOVES = "train_tokens_per_s"

from chipbench.readers import scope_ms_per_step  # noqa: E402


def read(record):
    return scope_ms_per_step(record, "tel.attention")
