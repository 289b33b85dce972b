"""What a timed entry hands back to ``run.py``, and how checks are judged."""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass
class Result:
    attempted: int
    failed: int
    end_to_end: dict                # name -> value
    record: dict                    # what per-layer readers read
    numbers: dict                   # name -> value compared for `correct`
    limits: dict                    # name -> limit (same keys)
    memory_peak_bytes: int
    busy_s: float | None = None     # traced runs only
    window_s: float | None = None
    breakdown: dict | None = None

    def checks(self) -> list:
        """[(name, value, limit, ok)]: a number passes at or under its
        limit; a missing or non-finite number fails."""
        out = []
        for name, limit in self.limits.items():
            v = self.numbers.get(name, float("nan"))
            ok = v is not None and math.isfinite(v) and v <= limit
            out.append((name, v, limit, ok))
        return out

    @property
    def correct(self) -> bool:
        return self.failed == 0 and all(ok for *_, ok in self.checks())


def device_peak_bytes() -> int:
    """Peak device memory of the fullest chip so far: the allocator's peak
    in use plus its peak reservation for programs' temporaries (this
    runtime reserves those apart from what it reports in use)."""
    import jax
    peak = 0
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0))
                   + int(st.get("peak_bytes_reserved", 0)))
    return peak
