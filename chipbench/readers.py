"""Shared arithmetic of the per-layer metric readers (`metrics/*.py`)."""
from __future__ import annotations

from chipbench import counts, trace  # noqa: F401


def traced(record) -> bool:
    """Whether the run was traced and the trace holds device ops."""
    events = record.get("events")
    return bool(events) and "window" in record and \
        any(e.line == "ops" for e in events)


def steps(record) -> int:
    """Train steps in the traced window (runs of the step program)."""
    lo, hi = record["window"]
    return len(trace.module_runs(record["events"], lo, hi, "jit_train_step"))


def scope_ms_per_step(record, scope: str):
    """ms per step of leaf ops whose named-scope path holds ``scope``."""
    if not traced(record) or not record.get("scopes"):
        return None
    n = steps(record)
    if n == 0:
        return None
    lo, hi = record["window"]
    sc = record["scopes"]
    t = trace.op_time_s(record["events"], lo, hi,
                        lambda name: scope in sc.get(name, ""))
    return 1e3 * t / n if t > 0 else None


# programs the serving engine compiles (their names in the trace)
DECODE_PROGRAM = "jit__step"
ADMIT_PROGRAMS = ("jit__pf", "jit__cm")


def serve_runs(record, program: str) -> list:
    """Device durations (s) of the runs of one serving program in the
    traced window."""
    if not traced(record):
        return []
    lo, hi = record["window"]
    return trace.module_runs(record["events"], lo, hi, program)
