"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run            # full suite
  PYTHONPATH=src python -m benchmarks.run --only table3,roofline
  PYTHONPATH=src python -m benchmarks.run --smoke    # CI: tiny speed sweep
                                                     # incl. the fused-update
                                                     # interpret path
"""
from __future__ import annotations

import argparse
import inspect
import sys

SUITES = {
    "memory": ("benchmarks.bench_memory", "Tables 1+2 (memory)"),
    "speed": ("benchmarks.bench_speed", "Table 5 (optimizer runtime)"),
    "qerror": ("benchmarks.bench_qerror", "Table 6 + App D (quant error)"),
    "ablation": ("benchmarks.bench_ablation",
                 "Table 3 + App H/I + Fig 3 (training ablations)"),
    "roofline": ("benchmarks.bench_roofline", "Dry-run roofline table"),
    "step_overlap": ("benchmarks.bench_step_overlap",
                     "Optimizer-exposed ms/step: sequential vs overlapped "
                     "ZeRO-2 (DESIGN.md §13)"),
    "telemetry": ("benchmarks.bench_telemetry",
                  "Telemetry JSONL + qhealth probe smoke (DESIGN.md §14)"),
    "analyze": ("benchmarks.bench_analyze",
                "Static VMEM budget table -> BENCH_speed.json "
                "(DESIGN.md §15)"),
    "serve": ("benchmarks.bench_serve",
              "Paged quantized KV serving: bytes/token + continuous-"
              "batching tokens/s + p50/p99 (DESIGN.md §17)"),
}

# Suites a --smoke run exercises (fast enough for CI, covers the kernels).
SMOKE_SUITES = ("speed",)


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated suite names")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced CI sweep (speed suite at tiny sizes, "
                         "fused kernels on the Pallas interpret path)")
    ap.add_argument("--bits", type=int, default=None,
                    help="also run the packed k-bit legs (4/5/6/8) of any "
                         "suite that supports a bitwidth sweep")
    ap.add_argument("--algo", type=str, default=None,
                    help="also run the algorithm-specific legs of any "
                         "suite that supports them (e.g. --algo muon runs "
                         "the Newton–Schulz matrix-optimizer sweep even "
                         "under --smoke; DESIGN.md §11)")
    ap.add_argument("--partition", action="store_true",
                    help="also run the ZeRO-1 partitioned-state legs "
                         "(per-device owned bytes + span launches vs "
                         "shard count, even under --smoke; DESIGN.md §12)")
    ap.add_argument("--overlap", action="store_true",
                    help="also run the step_overlap suite (optimizer-"
                         "exposed ms + ZeRO-2 peak grad bytes on a "
                         "4-device host mesh, even under --smoke; "
                         "DESIGN.md §13)")
    ap.add_argument("--analyze", action="store_true",
                    help="also run the static-analysis suite: the Pallas "
                         "VMEM budget table recorded to BENCH_speed.json "
                         "(headroom per kernel config), even under "
                         "--smoke (DESIGN.md §15)")
    ap.add_argument("--serve", action="store_true",
                    help="also run the serving suite: paged 8/4-bit KV "
                         "bytes/token and continuous-batching vs static-"
                         "bucket tokens/s with their gates (4-bit <= "
                         "0.30x fp16 bytes; continuous >= 1.5x static), "
                         "even under --smoke (DESIGN.md §17)")
    ap.add_argument("--telemetry", action="store_true",
                    help="also run the telemetry legs: the JSONL/qhealth "
                         "smoke suite (schema-validated probe artifact, "
                         "4-device mesh when forced) and the speed "
                         "suite's telemetry-overhead gates, even under "
                         "--smoke (DESIGN.md §14)")
    args = ap.parse_args()
    if args.only:
        names = args.only.split(",")
    elif args.smoke:
        names = list(SMOKE_SUITES)
    else:
        names = list(SUITES)
    if args.overlap and "step_overlap" not in names:
        names.append("step_overlap")
    if args.telemetry and "telemetry" not in names:
        names.append("telemetry")
    if args.analyze and "analyze" not in names:
        names.append("analyze")
    if args.serve and "serve" not in names:
        names.append("serve")
    print("name,us_per_call,derived")
    failed = []
    for n in names:
        mod_name, desc = SUITES[n]
        print(f"# === {n}: {desc} ===")
        mod = __import__(mod_name, fromlist=["main"])
        kwargs = {}
        params = inspect.signature(mod.main).parameters
        if args.smoke and "smoke" in params:
            kwargs["smoke"] = True
        if args.bits is not None and "bits" in params:
            kwargs["bits"] = args.bits
        if args.algo is not None and "algo" in params:
            kwargs["algo"] = args.algo
        if args.partition and "partition" in params:
            kwargs["partition"] = True
        if args.telemetry and "telemetry" in params:
            kwargs["telemetry"] = True
        try:
            mod.main(**kwargs)
        except Exception as e:  # run the other suites, then fail
            print(f"{n}/ERROR,0,{e!r}", file=sys.stderr)
            print(f"{n}/ERROR,0,{e!r}")
            failed.append(n)
    if failed:
        raise SystemExit(f"failed suites: {','.join(failed)}")


if __name__ == "__main__":
    main()
