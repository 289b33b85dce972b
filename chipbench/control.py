"""Readings that set a cell's limits, several seeds in one process.

    python3 -m chipbench.control --workload <name> --seeds 11 12 13 \
        [--program] [--control]

For each seed it prints one JSON line with the numbers that ``correct``
compares, read from:

  program   (``--program``) the program's own readings at the cell's size
            (train: its first three steps; serve: one wave): the lower
            readings that a limit must clear;
  control   (``--control``) the reference in the program's place at the
            next precision below the configuration's (bf16 compute ->
            fp8: e4m3 inputs and e5m2 gradients of every matrix product);
            it has to come out not correct;
  faults    (``--control``) planted faults: for a train cell half of each
            batch left out, the mean taken over the rest, and for an 8-bit
            one each stored second-moment code read back as the next code
            up (``grad`` reads the first moment only); for a serve
            cell one served token altered where it is produced.  A train
            step that returns its state unchanged reads 1 by construction
            and needs no run.

The benchmark's own runs never run this.  It needs the chip the cell
asks for.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

from chipbench import registry

sys.path.insert(0, str(registry.REPO_ROOT / "src"))


def train_readings(cell, seed, program: bool, controls: bool = True) -> dict:
    import numpy as np
    from chipbench.entries import train as T
    from chipbench import gen
    out = {}
    if program:
        prog, live = T.first_steps(cell, seed)
        del live
        gc.collect()
    from chipbench.reference import dense_lm
    key = dense_lm.make_key(seed)
    ring = gen.train_ring(cell.traffic, cell.config["vocab_size"], seed)
    ref = T.reference_readings(cell.config, cell.traffic, key, ring)
    g = np.asarray(ref["grad1"])
    out["left_out_of_update"] = [
        p for p, x in zip(ref["paths"], g)
        if x < T.ZERO_GRAD_SHARE * np.median(g)]
    if program:
        out["program"] = T.compare(prog, ref)
    if not controls:
        return out
    ctrl = T.reference_readings(cell.config, cell.traffic, key, ring,
                                precision="fp8")
    out["control"] = T.compare(ctrl, ref)
    half = T.reference_readings(cell.config, cell.traffic, key, ring,
                                fault="half_batch")
    out["half_batch"] = T.compare(half, ref)
    if T._bits(cell.traffic) == 8:
        shifted = T.reference_readings(cell.config, cell.traffic, key, ring,
                                       fault="r_neighbour_code")
        out["r_neighbour_code"] = T.compare(shifted, ref)
    out["state_unchanged"] = {"update": 1.0}
    return out


def serve_readings(cell, seed, program: bool, controls: bool = True) -> dict:
    import numpy as np
    from chipbench.entries import serve as S
    from chipbench import gen
    engine, params = S.build(cell, seed)
    traffic, config = cell.traffic, cell.config
    finished, *_ = S.serve_waves(engine, traffic, config["vocab_size"],
                                 seed, max_waves=1)
    del engine
    gc.collect()
    shapes = gen.wave_shapes(traffic)
    pad_to = max(p for p, _ in shapes) + max(m for _, m in shapes)
    sample = S.check_sample(traffic, finished, seed)
    out = {}
    if program:
        g = S.logit_gaps(config, params, sample, pad_to)
        out["program"] = {"logit_gap": float(max(x.max() for x in g))}
    if not controls:
        return out
    g = S.logit_gaps(config, params, sample, pad_to, judge="fp8")
    out["control"] = {"logit_gap": float(max(x.max() for x in g))}
    req, served = sample[0]
    bad = np.array(served)
    bad[len(bad) // 2] = (bad[len(bad) // 2] + 1) % config["vocab_size"]
    g = S.logit_gaps(config, params, [(req, bad)], pad_to)
    out["altered_token"] = {"logit_gap": float(g[0].max())}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    cell = registry.resolve(args.workload)
    import jax
    from chipbench import run
    run.enable_compile_cache(jax)
    run.device_info(jax, cell.chips, require_tpu=True)
    fn = {"train": train_readings, "serve": serve_readings}[
        cell.traffic["entry"]]
    for seed in args.seeds:
        line = {"workload": args.workload, "seed": seed,
                **fn(cell, seed, args.program, args.control)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
