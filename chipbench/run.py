"""Run one benchmark cell on the chip and print its result line.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix; the traffic file names the timed entry (``entries/<entry>.py``)
that sets up the program, runs the measured window and checks what the
timed path produced against the plain reference.  With ``--trace 0`` the
line carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.

The run needs a TPU with at least the cell's chips: without one it exits
with code 2 and prints no result.  The last lines of standard error, and
the result line's ``checks``, give every number compared with its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from chipbench import registry  # noqa: E402

sys.path.insert(0, str(registry.REPO_ROOT / "src"))

NO_CHIP_EXIT = 2


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_compile_cache(jax) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else ``<checkout>/.jax_cache`` (a fixed path, so the next run hits)."""
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir",
                          str(registry.REPO_ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def device_info(jax, chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if require_tpu and (info["platform"] != "tpu" or info["count"] < chips):
        print(f"error: the cell needs {chips} TPU chip(s); JAX sees "
              f"{info['count']} {info['platform']} device(s)",
              file=sys.stderr, flush=True)
        raise SystemExit(NO_CHIP_EXIT)
    return info


def result_line(cell, res, trace: bool, device: dict,
                bench_dir=registry.BENCH_DIR) -> dict:
    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = registry.metric_reader(m["name"], bench_dir).read(res.record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": res.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=res.memory_peak_bytes)
    if trace:
        dev.update(busy_s=res.busy_s, window_s=res.window_s)
    line = {"correct": res.correct, "attempted": res.attempted,
            "failed": res.failed, "metrics": metrics, "device": dev}
    if trace and res.breakdown:
        line["breakdown"] = res.breakdown
    line["checks"] = {name: {"value": v, "limit": lim}
                      for name, v, lim, _ in res.checks()}
    return line


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, root=registry.REPO_ROOT,
             bench_dir=registry.BENCH_DIR, t_start: float = None) -> dict:
    """Set up, measure and check one cell; returns the result line."""
    t_start = T_START if t_start is None else t_start
    cell = registry.resolve(workload, root, bench_dir)
    import jax
    enable_compile_cache(jax)
    device = device_info(jax, cell.chips, require_tpu)
    from chipbench import peaks
    peak = peaks.lookup(device["kind"]) if require_tpu else None
    entry = importlib.import_module(
        f"chipbench.entries.{cell.traffic['entry']}")
    res = entry.run(cell, seed=seed, seconds=seconds, trace=trace,
                     t_start=t_start, peaks=peak)
    return result_line(cell, res, trace, device, bench_dir), res


def main(argv=None) -> int:
    args = parse_args(argv)
    line, res = run_cell(args.workload, args.seed, args.seconds,
                         bool(args.trace))
    print(f"correct: {line['correct']} (attempted {line['attempted']}, "
          f"failed {line['failed']})", file=sys.stderr)
    for name, v, lim, ok in res.checks():
        print(f"check {name}: {v!r} limit {lim!r} {'ok' if ok else 'FAIL'}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
