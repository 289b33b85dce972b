"""Reduce a profiler trace to device busy time, per-scope and per-kernel
device time, exposed collective time and the longest idle gaps.

``load(logdir)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into
plain events; everything else works on those events, so the reduction is
tested on a small recorded trace (``tests/data``).  An event is
``(line, name, start_ns, dur_ns)`` with ``line`` one of:

    "ops"      an XLA op on a device (``XLA Ops``); name = HLO instruction
    "modules"  a compiled program's run on a device (``XLA Modules``)
    "host"     a host span the benchmark opened (names start with ``cb.``)

Device ops nest: a ``while`` op's event spans the ops of its body.  Busy
time is the union of all op intervals; op times count only the ops that
are not control flow (leaf ops), so nothing is counted twice.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import re

HOST_PREFIX = "cb."
CONTROL_FLOW = re.compile(r"^(while|conditional|call)(\.|$)")
COLLECTIVE = re.compile(
    r"^(all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all)")


@dataclasses.dataclass(frozen=True)
class Event:
    device: int           # device index; -1 for host spans
    line: str             # "ops" | "modules" | "host"
    name: str
    start: float          # ns
    dur: float            # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


def instruction(name: str) -> str:
    """'%fusion.12 = f32[...] fusion(...)' -> 'fusion.12'."""
    m = re.match(r"%?([^\s=]+)", name)
    return m.group(1) if m else name


def module(name: str) -> str:
    """'jit_train_step(1282...)' -> 'jit_train_step'."""
    return name.split("(", 1)[0]


def load(logdir: str) -> list:
    """Events of the one ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData
    files = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {logdir}, "
                           f"found {len(files)}")
    pd = ProfileData.from_file(files[0])
    out = []
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        for line in plane.lines:
            if m and line.name in ("XLA Ops", "XLA Modules"):
                kind = "ops" if line.name == "XLA Ops" else "modules"
                name_fn = instruction if kind == "ops" else module
                out += [Event(int(m.group(1)), kind, name_fn(e.name),
                              float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
            elif plane.name.startswith("/host:"):
                out += [Event(-1, "host", e.name, float(e.start_ns),
                              float(e.duration_ns))
                        for e in line.events
                        if e.name.startswith(HOST_PREFIX)]
    return out


# ------------------------------------------------------------ intervals

def union(intervals) -> list:
    """Merged, sorted [start, end] intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def covered(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside [lo, hi]."""
    return sum(max(0.0, min(e, hi) - max(s, lo))
               for s, e in union(intervals))


def leaf_ops(events, device: int) -> list:
    """The device's op events other than control flow (``while``,
    ``conditional``, ``call``), whose events span the ops of their
    bodies."""
    return [e for e in events if e.line == "ops" and e.device == device
            and not CONTROL_FLOW.match(e.name)]


def devices(events) -> list:
    return sorted({e.device for e in events if e.device >= 0})


def window(events, name: str) -> tuple:
    """(start, end) of the host span ``name`` (the traced window)."""
    spans = [e for e in events if e.line == "host" and e.name == name]
    if not spans:
        raise RuntimeError(f"no host span {name!r} in the trace")
    return min(e.start for e in spans), max(e.end for e in spans)


def busy_s(events, lo: float, hi: float) -> float:
    """Seconds in [lo, hi] in which some op ran, averaged over devices."""
    devs = devices(events)
    if not devs:
        return 0.0
    tot = 0.0
    for d in devs:
        tot += covered([(e.start, e.end) for e in events
                        if e.line == "ops" and e.device == d], lo, hi)
    return tot / len(devs) / 1e9


def _inside(e, lo, hi) -> bool:
    """Whether an event belongs to [lo, hi]: its middle lies inside (the
    device's clock is aligned to the host's to a few microseconds)."""
    return lo <= e.start + e.dur / 2 <= hi


def op_time_s(events, lo: float, hi: float, select) -> float:
    """Seconds of leaf ops in [lo, hi] for which ``select(name)`` holds,
    averaged over devices."""
    devs = devices(events)
    tot = sum(e.dur for d in devs for e in leaf_ops(events, d)
              if _inside(e, lo, hi) and select(e.name))
    return tot / max(len(devs), 1) / 1e9


def module_runs(events, lo: float, hi: float, name: str) -> list:
    """Durations (s) of the runs of program ``name`` on device 0."""
    d0 = min(devices(events), default=0)
    return [e.dur / 1e9 for e in events
            if e.line == "modules" and e.device == d0 and e.name == name
            and _inside(e, lo, hi)]


def exposed_collective_s(events, lo: float, hi: float) -> float:
    """Seconds of collective ops during which the same device runs no
    other op, averaged over devices."""
    devs = devices(events)
    tot = 0.0
    for d in devs:
        leaves = [e for e in leaf_ops(events, d) if _inside(e, lo, hi)]
        coll = [(e.start, e.end) for e in leaves if COLLECTIVE.match(e.name)]
        comp = union((e.start, e.end) for e in leaves
                     if not COLLECTIVE.match(e.name))
        for s, e in union(coll):
            tot += (e - s) - covered(comp, s, e)
    return tot / max(len(devs), 1) / 1e9


def top_ops(events, lo: float, hi: float, n: int = 10) -> list:
    """[[op family, seconds]] of the leaf ops that took most time, summed
    over the window and averaged over devices; the family is the
    instruction name without its numeric suffix."""
    devs = devices(events)
    acc = collections.Counter()
    for d in devs:
        for e in leaf_ops(events, d):
            if _inside(e, lo, hi):
                acc[re.sub(r"\.\d+$", "", e.name)] += e.dur / 1e9
    k = max(len(devs), 1)
    return [[name, t / k] for name, t in acc.most_common(n)]


def idle_gaps(events, lo: float, hi: float, n: int = 10) -> list:
    """[[host span, seconds]] of the longest gaps in [lo, hi] in which
    device 0 ran no op, each named by the innermost benchmark host span
    covering the gap's middle ('no host span' where none does)."""
    d0 = min(devices(events), default=0)
    busy = union((e.start, e.end) for e in events
                 if e.line == "ops" and e.device == d0)
    gaps, cursor = [], lo
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, min(s, hi)))
        cursor = max(cursor, e)
        if cursor >= hi:
            break
    if cursor < hi:
        gaps.append((cursor, hi))
    host = [e for e in events if e.line == "host"]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        spans = [h for h in host if h.start <= mid <= h.end]
        label = min(spans, key=lambda h: h.dur).name if spans \
            else "no host span"
        out.append([label, (e - s) / 1e9])
    return out


def scope_map(hlo_text: str) -> dict:
    """Instruction name -> ``op_name`` metadata of a compiled module's
    HLO text (``compiled.as_text()``), the named-scope path of each op."""
    out = {}
    for m in re.finditer(
            r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"",
            hlo_text, re.M):
        out[m.group(1)] = m.group(2)
    return out


class Profiled:
    """Context manager: profile the block under the host span
    ``cb.window`` into a temporary directory (under ``TMPDIR``), then
    hold the reduced-to-plain events in ``.events`` and delete the files.
    Disabled, it does nothing and ``.events`` stays None."""

    def __init__(self, enabled: bool):
        self.enabled, self.events, self._dir, self._span = enabled, None, \
            None, None

    def __enter__(self):
        if not self.enabled:
            return self
        import tempfile
        import jax
        self._dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("cb.window")
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        if not self.enabled:
            return False
        import shutil
        import jax
        try:
            self._span.__exit__(*exc)
            jax.profiler.stop_trace()
            if exc[0] is None:
                self.events = load(self._dir)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False


def span(name: str):
    """A benchmark host span (``cb.<name>``) in the profiler's trace."""
    import jax
    return jax.profiler.TraceAnnotation(HOST_PREFIX + name)


class ProgramLoads:
    """Counts the programs JAX compiles or loads from its persistent cache
    from now on (one event per compile request): a warmed-up window
    counts none."""

    EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == self.EVENT:
            self.n += 1
