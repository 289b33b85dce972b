#!/usr/bin/env python3
"""Smoke run of the 8-bit optimizer stack and the paged server on a TPU.

    python chip_smoke.py              # one chip: train + serve phases
    python chip_smoke.py --chips 4    # four chips: the ZeRO phase only

One process holds the chip for the whole run.  The script exits nonzero,
and prints no result line, when JAX finds no TPU (``JAX_PLATFORMS=cpu``
included) or when the repository's ``src/`` is not next to it.  It sets no
flags of its own: ``LIBTPU_INIT_ARGS`` is left as the machine sets it.

Phases (one chip):

* **train** — ``repro.launch.train.main`` at paper-lm-209m's registered
  widths (10 layers, d_model 1024, d_ff 8192, vocab 50264, seq 512),
  pooled ``adam8`` with the Pallas fused update, 10 steps.  The launcher's
  own optimizer (``launch.train.setup``) is then checked twice: its
  compiled train step must hold a ``tpu_custom_call`` (the fused Pallas
  update), and one of its updates is compared with the jnp oracle's
  (``make_optimizer("adam8", impl="jnp")``) on identical state and grads.
  Last, the same 10 steps are repeated with the oracle through
  ``train_loop.jit_train_step`` on the same batches and the same chip.
  Fails on a nonfinite loss, a missing kernel, or a disagreement (below).
* **serve** — the paged ``ContinuousBatchingEngine`` (what
  ``repro.launch.serve`` drives) at stablelm-1.6b's full width (bf16
  compute), 8-bit KV, 4 requests of mixed length, greedy; once with
  ``impl="pallas"`` and once with ``impl="jnp"``.  The tokens must be
  identical.

Phase (``--chips 4``):

* **zero** — paper-lm-209m at full width on a ``("data",)`` mesh of four
  chips: ``adamw8`` ZeRO-1, ZeRO-2 with 4 overlap buckets, and plain data
  parallelism at the same global batch (its optimizer runs the jnp update:
  XLA cannot partition a Mosaic kernel that sits outside a ``shard_map``).
  Losses must agree within ``ZERO_LOSS_TOL``.  Placement is read from the
  shards each device holds: the quantized arena (what ZeRO partitions)
  must sit on all four devices with at most ``OWNED_FRACTION_MAX`` of its
  replicated bytes on any one, and no device may hold more optimizer
  statistics than the optimizer's owned-state accounting says.  The fp32
  statistics of the stable-embedding override stay replicated by design,
  so all statistics together read about 0.58x per device.

Train agreement.  The single update isolates the kernel: the state
(after one oracle step, so its statistics are nonzero) and the grads are
the same for both, and the two differ only in how the f32 update
arithmetic is rounded (FMA contraction, division, square root) — which
can move an element across a code boundary, by one code.  So:

* every code that differs is a neighbour (one codebook step);
* at most ``CODE_DIFF_MAX`` = 1e-2 of the codes differ;
* ``|Δp_pallas - Δp_jnp| / |Δp_jnp|`` over the kernel-updated params is
  at most ``UPDATE_REL_TOL`` = 1e-2.

The 10-step runs also differ in their grads from the second step on (the
params differ after the first update), and near-zero statistics sit in
the densest part of the codebook, so they are compared by loss only:
at most ``TRAIN_LOSS_TOL`` = 1e-3 (absolute, on a loss near 11.3).

In interpret mode on the CPU the comparisons are bit-identical (0 codes
differ).  A kernel that decodes each code as its neighbour's value — the
smallest wrong lookup — fails all of them there: 57 % of the codes
differ, by up to 59 steps, the update by 4.7e-2, the 10-step loss by
7.6e-3 (d_model 256).

``ZERO_LOSS_TOL = 2e-3`` allows twice the loss tolerance: the ZeRO runs
(Pallas) and the data-parallel run (jnp) differ by the same update
rounding, plus the summation order of the cross-device gradient reduction.

The timings printed are smoke timings of a 10-step run, not a benchmark.
The last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
OUT_DIR = ROOT / "chiprun_out" / "chip_smoke"

TRAIN_ARCH = "paper-lm-209m"
SERVE_ARCH = "stablelm-1.6b"
TRAIN_STEPS = 10
TRAIN_BATCH = 4
TRAIN_SEQ = 512
TRAIN_LR = 1e-3
TRAIN_WARMUP = 2
TRAIN_SEED = 0
TRAIN_LOSS_TOL = 1e-3
CODE_DIFF_MAX = 1e-2
UPDATE_REL_TOL = 1e-2
ZERO_STEPS = 3
ZERO_LOSS_TOL = 2e-3
OWNED_FRACTION_MAX = 0.3


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ train
def train_argv(metrics_path: pathlib.Path, steps: int = TRAIN_STEPS,
               batch: int = TRAIN_BATCH, seq_len: int = TRAIN_SEQ,
               extra=()) -> list:
    return ["--arch", TRAIN_ARCH, "--optimizer", "adam8",
            "--steps", str(steps), "--batch", str(batch),
            "--seq-len", str(seq_len), "--lr", str(TRAIN_LR),
            "--warmup", str(TRAIN_WARMUP), "--seed", str(TRAIN_SEED),
            "--out", str(metrics_path), *extra]


def launcher_setup(argv):
    """The config, data pipeline, optimizer, its kwargs and hyper that
    ``repro.launch.train.main(argv)`` runs with (f32 compute, as the
    launcher forces), for the runs built here from the library API."""
    from repro.launch import train as train_launch

    args = train_launch.parse_args(argv)
    cfg, pipe, opt, opt_kw, hyper = train_launch.setup(args)
    return cfg, pipe, opt, opt_kw, hyper, args.steps


def _batch(pipe, i):
    import jax.numpy as jnp
    return {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}


def host_state(opt_state) -> dict:
    """Host copies of what ``compare_updates`` reads: the arena's codes per
    state slot (unpacked) and the masters of the pooled quantized leaves —
    the params whose update the fused kernel computes — as one f32
    vector."""
    import jax
    import numpy as np
    from repro.core.lowbit import PackedCodes, unpack_codes
    from repro.core.optim.base import PooledQuantLeaf

    arena = opt_state.arena
    codes = {}
    for slot, c in (("m", arena.codes_m), ("r", arena.codes_r)):
        if isinstance(c, PackedCodes):
            c = unpack_codes(c.packed, c.bits)
        if c is not None:
            codes[slot] = np.asarray(jax.device_get(c))
    masters = [l.master for l in jax.tree_util.tree_leaves(
        opt_state.leaves, is_leaf=lambda x: isinstance(x, PooledQuantLeaf))
        if isinstance(l, PooledQuantLeaf)]
    master = np.concatenate([np.asarray(jax.device_get(m), np.float32)
                             .reshape(-1) for m in masters])
    return {"codes": codes, "master": master}


def compare_updates(before: dict, pallas: dict, ref: dict) -> dict:
    """Codes and param update of the Pallas update against the jnp one,
    both applied to the same state and grads."""
    import numpy as np

    out = {"codes": 0, "codes_differing": 0, "max_code_step": 0}
    for slot, b in ref["codes"].items():
        d = np.abs(pallas["codes"][slot].astype(np.int16) - b.astype(np.int16))
        n_diff, step = int(np.count_nonzero(d)), int(d.max())
        out[f"codes_differing_{slot}"] = n_diff
        out[f"max_code_step_{slot}"] = step
        out["codes"] += d.size
        out["codes_differing"] += n_diff
        out["max_code_step"] = max(out["max_code_step"], step)
    out["code_diff_fraction"] = out["codes_differing"] / out["codes"]
    dp = pallas["master"] - before["master"]
    dj = ref["master"] - before["master"]
    out["update_rel_diff"] = float(np.linalg.norm(dp - dj)
                                   / np.linalg.norm(dj))
    return out


def compiled_step_text(cfg, opt, hyper, pipe) -> str:
    """HLO of ``opt``'s compiled train step, lowered from shapes only."""
    import jax
    from repro.train import loop as train_loop

    step_fn = train_loop.jit_train_step(cfg, opt, hyper)
    state = jax.eval_shape(lambda: train_loop.init_train_state(
        cfg, opt, jax.random.PRNGKey(TRAIN_SEED))[0])
    return step_fn.lower(state, _batch(pipe, 0)).compile().as_text()


def update_comparison(cfg, opt, opt_ref, hyper, pipe) -> dict:
    """One optimizer update by ``opt`` and by ``opt_ref`` on identical
    inputs.  ``opt_ref`` first takes a train step, so the statistics the
    update decodes are nonzero; the grads of batch 1 at the resulting
    params (clipped as the train step clips them) are taken once, and
    both optimizers apply them to that state at the schedule's lr."""
    import jax
    from repro.train import loop as train_loop

    state, _ = train_loop.init_train_state(cfg, opt_ref,
                                           jax.random.PRNGKey(TRAIN_SEED))
    state, _ = train_loop.jit_train_step(cfg, opt_ref, hyper)(
        state, _batch(pipe, 0))
    loss_fn = train_loop.make_loss_fn(cfg, hyper)

    @jax.jit
    def grads_at(opt_state, batch):
        params = opt_ref.params_view(opt_state)
        g = jax.grad(lambda p: loss_fn(p, batch)[0])(params)
        return train_loop.clip_by_global_norm(g, hyper.grad_clip)[0]

    grads = grads_at(state.opt_state, _batch(pipe, 1))
    lr = hyper.lr_schedule(state.step)
    before = host_state(state.opt_state)
    after = {}
    for name, o in (("pallas", opt), ("jnp", opt_ref)):
        new = jax.jit(lambda g, s, o=o: o.apply(
            g, s, lr=lr, materialize_params=False)[1])(grads, state.opt_state)
        after[name] = host_state(new)
        del new
    return compare_updates(before, after["pallas"], after["jnp"])


def run_oracle(cfg, opt, hyper, pipe, steps: int, seed: int = TRAIN_SEED):
    """Train ``steps`` steps with ``opt`` on the launcher's batches."""
    import jax
    from repro.train import loop as train_loop

    step_fn = train_loop.jit_train_step(cfg, opt, hyper)
    state, _ = train_loop.init_train_state(cfg, opt, jax.random.PRNGKey(seed))
    losses = []
    for i in range(steps):
        state, metrics = step_fn(state, _batch(pipe, i))
        losses.append(float(metrics["loss"]))
    return losses


def train_phase(argv, *, expect_kernel: bool = True) -> dict:
    from repro.core.optim import make_optimizer
    from repro.launch import train as train_launch

    metrics_path = pathlib.Path(argv[argv.index("--out") + 1])
    metrics_path.parent.mkdir(parents=True, exist_ok=True)
    if metrics_path.exists():
        metrics_path.unlink()     # the launcher appends
    cfg, pipe, opt, opt_kw, hyper, steps = launcher_setup(argv)
    log(f"[train] {TRAIN_ARCH}: n_layers={cfg.n_layers} "
        f"d_model={cfg.d_model} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
        f"seq={pipe.cfg.seq_len} batch={pipe.cfg.global_batch} "
        f"param_dtype={cfg.param_dtype} compute_dtype={cfg.compute_dtype} "
        f"(the launcher forces f32 compute)")

    t0 = time.perf_counter()
    rc = train_launch.main(argv)
    wall = time.perf_counter() - t0
    check(rc == 0, f"repro.launch.train.main returned {rc}")
    recs = [json.loads(line) for line in metrics_path.read_text().splitlines()]
    check(len(recs) == steps, f"{len(recs)} metric records, want {steps}")
    losses = [r["loss"] for r in recs]
    check(all(math.isfinite(x) for x in losses),
          f"nonfinite loss in the Pallas run: {losses}")
    compile_s = recs[0]["compile_s"]
    steady = sorted(r["t"] for r in recs[1:])
    steady_ms = 1e3 * steady[len(steady) // 2] if steady else float("nan")

    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"[train] launcher wall {wall:.1f}s; compile {compile_s:.2f}s; "
        f"steady {steady_ms:.1f} ms/step (median of {len(steady)} steps; "
        f"smoke timing, not a benchmark); peak_bytes_in_use={peak}")
    log(f"[train] pallas losses: {losses}")

    text = compiled_step_text(cfg, opt, hyper, pipe)
    has_kernel = "tpu_custom_call" in text
    log(f"[train] compiled step of the launcher's optimizer has "
        f"tpu_custom_call: {has_kernel} "
        f"({text.count('tpu_custom_call')} occurrences)")
    if expect_kernel:
        check(has_kernel, "the compiled train step has no tpu_custom_call: "
                          "the fused Pallas update is not inside it")

    opt_ref = make_optimizer("adam8", impl="jnp", **opt_kw)
    cmp = update_comparison(cfg, opt, opt_ref, hyper, pipe)
    log(f"[train] one update, launcher's optimizer vs jnp, same state and "
        f"grads: {cmp['codes_differing']} of {cmp['codes']} codes differ "
        f"(m {cmp['codes_differing_m']}, r {cmp['codes_differing_r']}; "
        f"fraction {cmp['code_diff_fraction']:.3e}, bound "
        f"{CODE_DIFF_MAX:g}); largest code index step "
        f"{cmp['max_code_step']} (m {cmp['max_code_step_m']}, r "
        f"{cmp['max_code_step_r']}; bound 1); |update diff| / |update| = "
        f"{cmp['update_rel_diff']:.3e} (bound {UPDATE_REL_TOL:g})")
    check(cmp["max_code_step"] <= 1,
          f"a code differs by {cmp['max_code_step']} codebook steps")
    check(cmp["code_diff_fraction"] <= CODE_DIFF_MAX,
          f"{cmp['code_diff_fraction']:.3e} of the codes differ")
    check(cmp["update_rel_diff"] <= UPDATE_REL_TOL,
          f"param updates differ by {cmp['update_rel_diff']:.3e}")

    t0 = time.perf_counter()
    oracle = run_oracle(cfg, opt_ref, hyper, pipe, steps)
    log(f"[train] jnp oracle losses: {oracle} "
        f"({time.perf_counter() - t0:.1f}s incl. compile)")
    check(all(math.isfinite(x) for x in oracle),
          f"nonfinite loss in the jnp run: {oracle}")

    diff = max(abs(a - b) for a, b in zip(losses, oracle))
    log(f"[train] max |loss pallas - loss jnp| = {diff:.3e} "
        f"(tolerance {TRAIN_LOSS_TOL:g})")
    check(diff <= TRAIN_LOSS_TOL,
          f"Pallas and jnp losses differ by {diff:.3e} > {TRAIN_LOSS_TOL}")
    log(f"[train] loss {losses[0]:.4f} -> {losses[-1]:.4f} over "
        f"{steps} steps")
    return {"compile_s": compile_s, "steady_ms": steady_ms,
            "peak_bytes_in_use": peak, "max_loss_diff": diff, **cmp}


# ------------------------------------------------------------------ serve
SERVE_PROMPT_LENS = "16,40"
SERVE_REQUESTS = 4
SERVE_MAX_NEW = 24


def serve_phase(cfg=None, impls=("pallas", "jnp")) -> dict:
    import jax
    import numpy as np
    from repro.configs import base as cfgs
    from repro.launch.serve import build_requests
    from repro.models import model as M
    from repro.serve.kvcache import PagedKVConfig
    from repro.serve.scheduler import (ContinuousBatchingEngine,
                                       SchedulerConfig)

    cfg = cfg or cfgs.get_config(SERVE_ARCH)
    log(f"[serve] {cfg.arch_id}: n_layers={cfg.n_layers} "
        f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
        f"head_dim={cfg.head_dim} vocab={cfg.vocab_size} "
        f"param_dtype={cfg.param_dtype} compute_dtype={cfg.compute_dtype}; "
        f"8-bit paged KV, greedy")
    params, _ = M.init_model(cfg, jax.random.PRNGKey(0))
    reqs = build_requests(argparse.Namespace(
        seed=0, prompt_lens=SERVE_PROMPT_LENS, streams=SERVE_REQUESTS,
        max_new=SERVE_MAX_NEW, uniform_new=False), cfg.vocab_size)
    log(f"[serve] requests (prompt, new): "
        f"{[(len(r.prompt), r.max_new_tokens) for r in reqs]}")
    kv = PagedKVConfig(page_size=16, n_pages=64, n_slots=4,
                       max_pages_per_seq=8, kv_bits=8)
    tokens, out = {}, {}
    for impl in impls:
        eng = ContinuousBatchingEngine(cfg, params, SchedulerConfig(
            kv=kv, temperature=0.0, seed=0, impl=impl))
        t0 = time.perf_counter()
        res = eng.serve(reqs)
        wall = time.perf_counter() - t0
        tokens[impl] = {rid: np.asarray(t).tolist() for rid, t in res.items()}
        n_tok = sum(len(t) for t in tokens[impl].values())
        log(f"[serve] impl={impl}: {n_tok} tokens in {wall:.1f}s incl. "
            f"compile (smoke timing, not a benchmark); "
            f"request 0 -> {tokens[impl][0][:8]}")
        out[f"{impl}_wall_s"] = wall
        for r in reqs:
            check(len(tokens[impl][r.rid]) == r.max_new_tokens,
                  f"request {r.rid} produced {len(tokens[impl][r.rid])} "
                  f"tokens, want {r.max_new_tokens}")
    ref = tokens[impls[-1]]
    for impl in impls[:-1]:
        same = tokens[impl] == ref
        log(f"[serve] {impl} tokens identical to {impls[-1]}: {same}")
        check(same, f"greedy tokens differ between {impl} and {impls[-1]}")
    return out


# ------------------------------------------------------------------- zero
def _zero_runs(mesh, cfg, opt_kw, hyper, pipe, steps):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.optim import make_optimizer
    from repro.train import loop as train_loop

    runs = {
        "zero1": dict(mesh=mesh),
        "zero2_overlap4": dict(mesh=mesh, shard_grads=True,
                               overlap_buckets=4),
        # XLA cannot partition a Mosaic kernel inside an SPMD program, so
        # the unpartitioned optimizer on a mesh runs the jnp update.
        "data_parallel": dict(partition=False, impl="jnp"),
    }
    rep = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P("data"))
    results = {}
    for name, kw in runs.items():
        opt = make_optimizer("adamw8", **opt_kw, **kw)
        step_fn = train_loop.jit_train_step(cfg, opt, hyper)
        state, _ = train_loop.init_train_state(cfg, opt,
                                               jax.random.PRNGKey(TRAIN_SEED))
        state = jax.device_put(state, rep)
        losses, t0 = [], time.perf_counter()
        for i in range(steps):
            batch = {k: jax.device_put(jnp.asarray(v), data)
                     for k, v in pipe.batch_at(i).items()}
            state, metrics = step_fn(state, batch)
            losses.append(float(metrics["loss"]))
        log(f"[zero] {name}: losses {losses} "
            f"({time.perf_counter() - t0:.1f}s incl. compile)")
        check(all(math.isfinite(x) for x in losses),
              f"{name}: nonfinite loss {losses}")
        results[name] = (losses, state.opt_state, opt)
    return results


def placement(arrays, devices) -> dict:
    """Per-device bytes of ``arrays``, from the shards each device actually
    holds, against their global (replicated) bytes."""
    total = sum(int(x.nbytes) for x in arrays)
    per_dev = {d.id: 0 for d in devices}
    for x in arrays:
        for s in x.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) \
                + int(s.data.nbytes)
    return {"total_bytes": total, "per_device_bytes": per_dev,
            "max_fraction": max(per_dev.values()) / total}


def statistics_arrays(opt_state) -> dict:
    """The optimizer statistics ``state_bytes`` counts, by kind: the
    quantized arena (codes + absmax, what ZeRO partitions) and the fp32
    statistics (m/r of the fp32 pool and of the Full32 override leaves —
    the stable embedding — which stay replicated).  Masters are params and
    are not statistics."""
    import jax
    from repro.core.lowbit import PackedCodes
    from repro.core.optim.base import Full32Leaf

    arena = [x.packed if isinstance(x, PackedCodes) else x
             for x in jax.tree_util.tree_leaves(
                 opt_state.arena,
                 is_leaf=lambda x: isinstance(x, PackedCodes))]
    fp32 = []
    if opt_state.pool32 is not None:
        fp32 += [opt_state.pool32.m, opt_state.pool32.r]
    for leaf in jax.tree_util.tree_leaves(
            opt_state.leaves, is_leaf=lambda x: isinstance(x, Full32Leaf)):
        if isinstance(leaf, Full32Leaf):
            fp32 += [leaf.m, leaf.r]
    return {"arena": arena, "all": arena + [x for x in fp32
                                            if x is not None]}


def zero_phase(cfg=None, steps: int = ZERO_STEPS, n_chips: int = 4) -> dict:
    import jax
    from repro.launch.mesh import make_mesh

    devices = jax.devices()[:n_chips]
    check(len(devices) == n_chips,
          f"--chips {n_chips} needs {n_chips} devices, JAX has "
          f"{len(jax.devices())}")
    mesh = make_mesh((n_chips,), ("data",), devices=devices)
    argv = train_argv(OUT_DIR / "unused.jsonl", steps=steps)
    cfg0, pipe, _, opt_kw, hyper, _ = launcher_setup(argv)
    cfg = cfg or cfg0
    opt_kw = dict(opt_kw, weight_decay=0.01)
    log(f"[zero] {cfg.arch_id}: n_layers={cfg.n_layers} "
        f"d_model={cfg.d_model} d_ff={cfg.d_ff} vocab={cfg.vocab_size}; "
        f"global batch {pipe.cfg.global_batch} x seq {pipe.cfg.seq_len} "
        f"over a ({n_chips},) data mesh")
    results = _zero_runs(mesh, cfg, opt_kw, hyper, pipe, steps)
    ref = results["data_parallel"][0]
    out = {}
    for name in ("zero1", "zero2_overlap4"):
        losses, st, opt = results[name]
        diff = max(abs(a - b) for a, b in zip(losses, ref))
        log(f"[zero] {name}: max |loss - data_parallel loss| = {diff:.3e} "
            f"(tolerance {ZERO_LOSS_TOL:g})")
        check(diff <= ZERO_LOSS_TOL,
              f"{name} losses differ from data parallel by {diff:.3e}")
        stats = statistics_arrays(st)
        arena = placement(stats["arena"], devices)
        whole = placement(stats["all"], devices)
        holders = sorted(d for d, b in arena["per_device_bytes"].items()
                         if b)
        sb = opt.state_bytes(st)
        log(f"[zero] {name}: arena bytes per device "
            f"{arena['per_device_bytes']} of {arena['total_bytes']} "
            f"replicated (max {arena['max_fraction']:.3f}x); devices "
            f"holding shards {holders}")
        log(f"[zero] {name}: all statistics (arena + replicated fp32 "
            f"m/r) bytes per device {whole['per_device_bytes']} of "
            f"{whole['total_bytes']} replicated (max "
            f"{whole['max_fraction']:.3f}x); static accounting "
            f"owned/total {sb.get('owned_state_bytes')}/"
            f"{sb.get('state_bytes')}")
        check(len(holders) == n_chips,
              f"{name}: arena shards sit on devices {holders}, not all "
              f"{n_chips}")
        check(arena["max_fraction"] <= OWNED_FRACTION_MAX,
              f"{name}: a device holds {arena['max_fraction']:.3f}x of the "
              f"replicated arena bytes > {OWNED_FRACTION_MAX}")
        check(max(whole["per_device_bytes"].values())
              <= sb["owned_state_bytes"],
              f"{name}: a device holds more statistics bytes than the "
              f"optimizer's owned-state accounting "
              f"({sb['owned_state_bytes']})")
        out[name] = {"max_loss_diff": diff,
                     "arena_fraction": arena["max_fraction"],
                     "statistics_fraction": whole["max_fraction"]}
    return out


# ------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the ZeRO phase on a four-chip mesh")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 1
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}; jax {jax.__version__}; "
        f"compile cache {cache_dir}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            zero_phase()
            log("[zero] PASS")
        else:
            train_phase(train_argv(OUT_DIR / "train_metrics.jsonl"))
            log("[train] PASS")
            serve_phase()
            log("[serve] PASS")
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    log(f"[done] {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
