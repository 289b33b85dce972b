"""Step-phase tracing + the shared step-timing helper (DESIGN.md §14).

One process-wide flag (:func:`set_phase_tracing`, default off) switches
the scopes and the spans; off, they add nothing to a compiled program and
open nothing on the host.

**Device scopes** — :func:`annotate` names a region of a traced function
(the optimizer step's phases: blockwise quant/dequant, per-bucket
``fused_update`` dispatches, Newton–Schulz gram/apply passes,
reduce-scatter, deferred all-gather; the model's ``attention``/``mlp``
and the paged cache's ``kv_append``/``kv_gather``).  Off, the wrapper is
a literal no-op (``yield`` and nothing else), so the jitted computation —
and its StableHLO text — is byte-identical to a build without telemetry
(the zero-overhead guards in tests/test_telemetry.py and
tests/test_serve_tracing.py pin this).  On, each ``annotate`` block:

  * enters ``jax.named_scope("tel.<phase>")``, which rides the ops'
    ``op_name`` metadata into the compiled HLO (it changes no value and
    no instruction name), and
  * records a *trace event*: ``(phase, fused dispatches inside, trace
    wall-clock)``.  Under jit this fires at trace time, so one compiled
    step yields one dispatch-accounted phase list — exactly the launches
    baked into the executable (the same convention as
    ``ops.fused_update_count``; DESIGN.md §10).

**Host spans** — :func:`span` opens a ``jax.profiler.TraceAnnotation``
named ``tel.<name>`` around host work at run time (the serving engine's
admission, page extension, uploads, decode bursts and completions), so
it lands on the profiler's host plane on the device trace's clock.  It
keeps nothing in Python, so a long-running server does not grow.

**Host wall-clock** — :class:`StepTimer` is the single definition of
``ms/step`` and ``compile_s``: the first executed step pays jit tracing +
XLA compilation and is reported apart (``compile_s``), steady-state steps
accumulate into ``ms/step``, and a trailing-window z-score flags
stragglers.  ``train/loop.py``-era call sites (``launch/train.py``,
quickstart, benchmarks) all use this one helper instead of inlining the
split.  :func:`host_phase` times host-side phases (probe runs, eval) into
"phase" events for the JSONL timeline, under the same span.
"""
from __future__ import annotations

import contextlib
import time
from typing import List, Optional

import numpy as np

_PHASE_TRACING = [False]
_TRACE_EVENTS: List[dict] = []
_PHASE_EVENTS: List[dict] = []


def set_phase_tracing(enabled: bool) -> None:
    """Turn trace-time phase annotation on/off (process-wide, default off).
    Flip BEFORE tracing/jitting the step: the flag is read at trace time,
    so already-compiled executables keep whatever the flag was when they
    were traced."""
    _PHASE_TRACING[0] = bool(enabled)


def phase_tracing_enabled() -> bool:
    return _PHASE_TRACING[0]


@contextlib.contextmanager
def phase_tracing(enabled: bool = True):
    """Scoped :func:`set_phase_tracing` (restores the prior flag)."""
    prev = _PHASE_TRACING[0]
    _PHASE_TRACING[0] = bool(enabled)
    try:
        yield
    finally:
        _PHASE_TRACING[0] = prev


def trace_events() -> list:
    """Trace events recorded since :func:`reset_trace_events` — one dict
    ``{"phase", "dispatches", "trace_s"}`` per annotated region entered
    while tracing.  Nested regions appear as separate entries (outer spans
    include inner dispatches)."""
    return list(_TRACE_EVENTS)


def reset_trace_events() -> None:
    _TRACE_EVENTS.clear()


@contextlib.contextmanager
def annotate(phase: str):
    """Name one region of a traced function.  A no-op unless phase tracing
    is enabled — keeping the default trace, and therefore the compiled
    program, untouched.  Enabled, it enters ``jax.named_scope`` and
    records a trace event with the number of fused_update dispatches
    issued inside the region (trace-time accounting, DESIGN.md §10)."""
    if not _PHASE_TRACING[0]:
        yield
        return
    import jax
    from repro.kernels import ops  # lazy: ops imports this module
    n0 = ops.fused_update_count()
    t0 = time.perf_counter()
    with jax.named_scope(f"tel.{phase}"):
        yield
    _TRACE_EVENTS.append({
        "phase": phase,
        "dispatches": ops.fused_update_count() - n0,
        "trace_s": time.perf_counter() - t0,
    })


def trace_event_dict(step: int) -> dict:
    """One "trace" JSONL event summarizing the recorded trace events (the
    per-phase dispatch accounting of the step compiled at ``step``)."""
    return {"kind": "trace", "step": int(step),
            "phases": [dict(e) for e in _TRACE_EVENTS]}


# ------------------------------------------------------ host-side timeline
def span(name: str, **ids):
    """A host span ``tel.<name>`` on the profiler's timeline, carrying
    ``ids`` (say ``rid=3``) as its metadata, when phase tracing is
    enabled; ``contextlib.nullcontext()`` otherwise.  Read at run time:
    unlike :func:`annotate`, it needs no re-trace to switch."""
    if not _PHASE_TRACING[0]:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(f"tel.{name}", **ids)


@contextlib.contextmanager
def host_phase(phase: str, step: int = -1):
    """Record host wall-clock for one phase into the pending "phase" event
    list (drained by :func:`drain_phase_events`), under :func:`span`."""
    t0 = time.perf_counter()
    try:
        with span(phase):
            yield
    finally:
        _PHASE_EVENTS.append({"kind": "phase", "step": int(step),
                              "phase": phase,
                              "wall_s": time.perf_counter() - t0})


def drain_phase_events() -> list:
    evs, _PHASE_EVENTS[:] = list(_PHASE_EVENTS), []
    return evs


class StepTimer:
    """The single ms/step + compile_s definition (PR-6 convention).

    The first recorded step is the compile step: its wall time is stored
    as ``compile_s`` and EXCLUDED from the steady-state series, because it
    pays jit tracing + XLA compilation and would otherwise skew ms/step
    and the straggler z-scores.  Subsequent steps append to ``times``.

        timer = StepTimer()
        for i in range(steps):
            with timer.step():
                ... run one step, block on the result ...
            if timer.straggler_z is not None and timer.straggler_z > 4: ...
    """

    def __init__(self, window: int = 20, z_threshold: float = 4.0):
        self.window = int(window)
        self.z_threshold = float(z_threshold)
        self.compile_s: Optional[float] = None
        self.times: List[float] = []
        self.last_dt: Optional[float] = None
        self.straggler_z: Optional[float] = None

    @contextlib.contextmanager
    def step(self):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.record(time.perf_counter() - t0)

    def record(self, dt: float) -> float:
        """Record one step's wall time; returns it.  First call lands in
        ``compile_s``, later calls in the steady series."""
        dt = float(dt)
        self.last_dt = dt
        self.straggler_z = None
        if self.compile_s is None:
            self.compile_s = dt
            return dt
        # straggler detection: z-score over the trailing window,
        # computed against the window BEFORE this step
        if len(self.times) > self.window:
            w = np.array(self.times[-self.window:-1])
            std = float(w.std())
            # A zero-variance window has no scale to judge deviation
            # against — the epsilon-divide made any jump look like a
            # billions-sigma straggler (or NaN).  Report 0.0: "no
            # evidence", not "infinite evidence".
            self.straggler_z = (float((dt - w.mean()) / std)
                                if std > 0.0 else 0.0)
        self.times.append(dt)
        return dt

    @property
    def is_straggler(self) -> bool:
        return (self.straggler_z is not None
                and self.straggler_z > self.z_threshold)

    def steady_ms(self) -> float:
        """Mean steady-state step time in ms (nan before the 2nd step)."""
        return 1e3 * float(np.mean(self.times)) if self.times else float("nan")

    def summary(self) -> dict:
        return {"compile_s": self.compile_s, "steady_ms": self.steady_ms(),
                "n_steps": len(self.times) + (self.compile_s is not None)}
