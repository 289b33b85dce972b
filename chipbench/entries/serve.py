"""Serve entry: the program's ``ContinuousBatchingEngine.serve()``, timed,
then checked.

Set-up makes the weights on the device from the seed, builds the engine
with the cell's paged KV cache and warms up every program a wave runs
(``warm_up``), so that nothing compiles in the window.  The window
serves successive waves (``gen.wave_shapes``: the same prompt and output
lengths in every wave and run; prompts drawn from the seed) back to back
while it lasts; the wave in flight at its end runs to completion and
counts.  ``serve_tokens_per_s`` is the tokens generated over that time.

After the window, with the engine freed, a sample of the finished requests
drawn from the seed, the one with the most tokens among them, goes through
the plain reference once each (prompt and served tokens, teacher-forced).
The number compared, ``logit_gap``, is the widest gap by which a served
token's reference logit lies below the reference's best at its position;
greedy decoding makes every served token the program's own argmax.
"""
from __future__ import annotations

import gc
import importlib
import sys
import time

import numpy as np

from chipbench import gen, modelcfg
from chipbench import trace as tr
from chipbench.result import Result, device_peak_bytes


def _ref_module(config):
    return importlib.import_module(f"chipbench.reference.{config['reference']}")


def wave_requests(traffic, vocab, seed, wave):
    from repro.serve.scheduler import Request
    shapes = gen.wave_shapes(traffic)
    prompts = gen.wave_prompts(traffic, vocab, seed, wave)
    base = wave * len(shapes)
    return [Request(rid=base + i, prompt=tuple(int(t) for t in p),
                    max_new_tokens=m)
            for i, (p, (_, m)) in enumerate(zip(prompts, shapes))]


def check_sample(traffic, finished, seed) -> list:
    """Requests to compare, drawn from the seed: the one with the most
    served tokens, and ``check_requests - 1`` others."""
    rng = np.random.default_rng([seed, 7])
    order = sorted(finished, key=lambda x: x[0].rid)
    longest = max(range(len(order)), key=lambda i: (len(order[i][1]), -i))
    rest = [i for i in range(len(order)) if i != longest]
    k = min(traffic["check_requests"] - 1, len(rest))
    pick = [longest] + list(rng.choice(rest, size=k, replace=False))
    return [order[i] for i in pick]


def logit_gaps(config, params, sample, pad_to, precision="f32",
               judge=None) -> list:
    """Per request, the gaps (reference best - reference logit of the
    token) at each served position.  The token is the served one, or with
    ``judge`` (a precision) the argmax of the reference run at ``judge``:
    the control reads the token a lower precision puts first."""
    import jax
    import jax.numpy as jnp
    ref = _ref_module(config)
    out = []
    with jax.default_matmul_precision("highest"):
        for req, served in sample:
            P, n = len(req.prompt), len(served)
            seq = np.concatenate([np.asarray(req.prompt, np.int32),
                                  np.asarray(served[:-1], np.int32)])
            lg = ref.sequence_logits(config, params, seq, pad_to,
                                     precision)[P - 1:P - 1 + n]
            if judge is None:
                tok = jnp.asarray(served, jnp.int32)
            else:
                tok = jnp.argmax(ref.sequence_logits(
                    config, params, seq, pad_to, judge)[P - 1:P - 1 + n], -1)
            best = jnp.max(lg, axis=-1)
            mine = jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
            out.append(np.asarray(best - mine, np.float64))
    return out


def build(cell, seed):
    import jax
    from repro.models import model as M
    from repro.serve.kvcache import PagedKVConfig
    from repro.serve.scheduler import ContinuousBatchingEngine, \
        SchedulerConfig
    from repro.telemetry import MetricRegistry
    config, traffic = cell.config, cell.traffic
    mcfg = modelcfg.program_config(config)
    ref = _ref_module(config)
    want = jax.tree_util.tree_map(
        lambda s: tuple(s.shape),
        jax.eval_shape(lambda: M.init_model(mcfg, jax.random.PRNGKey(0))[0]))
    if want != ref.param_shapes(config):
        raise ValueError("the program's parameter tree differs from the "
                         "reference's")
    params = jax.jit(lambda k: ref.init_params(config, k))(ref.make_key(seed))
    # the engine's own counters (admissions, preemptions, tokens)
    engine = ContinuousBatchingEngine(mcfg, params, SchedulerConfig(
        kv=PagedKVConfig(**traffic["kv"]), temperature=0.0, seed=0),
        registry=MetricRegistry())
    return engine, params


def warm_up(engine, traffic, vocab, seed):
    """Compile every program a wave runs, with as little work as will do.
    A wave that fits the slots is admitted at once and takes one decode
    step fewer than its longest output; the step is one program whatever
    the slots hold.  So one request per prompt length, two tokens each,
    runs the prefills, page commits, sampling and the decode step; what
    is left is the wave's one stack of its decode steps' token vectors,
    compiled here for the wave's number of steps."""
    import jax.numpy as jnp
    from repro.serve.scheduler import Request
    shapes = gen.wave_shapes(traffic)
    n_slots = traffic["kv"]["n_slots"]
    if len(shapes) > n_slots:
        raise ValueError(f"a wave of {len(shapes)} requests does not fit "
                         f"{n_slots} slots: the warm-up would not compile "
                         f"all of its programs")
    prompts = sorted({p for p, _ in shapes})
    engine.serve([Request(rid=10 ** 7 + i, prompt=tuple(range(1, p + 1)),
                          max_new_tokens=2)
                  for i, p in enumerate(prompts)])
    steps = max(m for _, m in shapes) - 1
    tok = jnp.zeros((n_slots,), jnp.int32)
    jnp.stack([tok] * steps).block_until_ready()


def serve_waves(engine, traffic, vocab, seed, *, max_waves=None,
                seconds=None, t0=None):
    """Serve waves back to back until ``max_waves`` are done or
    ``seconds`` have passed since ``t0`` (the wave in flight completes).
    Returns (finished [(request, tokens)], attempted, failed, tokens,
    waves)."""
    finished, attempted, failed, tokens, n_waves = [], 0, 0, 0, 0
    while True:
        reqs = wave_requests(traffic, vocab, seed, n_waves)
        with tr.span("serve_wave"):
            out = engine.serve(reqs)
        n_waves += 1
        attempted += len(reqs)
        for r in reqs:
            got = out.get(r.rid)
            if got is None or len(got) != r.max_new_tokens:
                failed += 1
            else:
                finished.append((r, np.asarray(got)))
                tokens += len(got)
        if (max_waves is not None and n_waves >= max_waves) or \
                (seconds is not None and time.perf_counter() - t0 >= seconds):
            return finished, attempted, failed, tokens, n_waves


def run(cell, *, seed, seconds, trace, t_start, peaks):
    import jax
    config, traffic = cell.config, cell.traffic
    vocab = config["vocab_size"]
    engine, params = build(cell, seed)
    warm_up(engine, traffic, vocab, seed)
    setup_s = time.perf_counter() - t_start
    loads = tr.ProgramLoads()

    with tr.Profiled(trace) as prof:
        t0 = time.perf_counter()
        finished, attempted, failed, tokens, n_waves = serve_waves(
            engine, traffic, vocab, seed,
            max_waves=1 if trace else None, seconds=seconds, t0=t0)
        t1 = time.perf_counter()
    print(f"serve: {loads.n} programs compiled or loaded in the window",
          file=sys.stderr, flush=True)
    peak_bytes = device_peak_bytes()
    counters = {name: engine.registry.counter(name).value for name in (
        "serve/sched/admitted", "serve/sched/evictions",
        "serve/generated_tokens")}
    del engine
    gc.collect()

    shapes = gen.wave_shapes(traffic)
    pad_to = max(p for p, _ in shapes) + max(m for _, m in shapes)
    sample = check_sample(traffic, finished, seed) if finished else []
    gaps = logit_gaps(config, params, sample, pad_to)
    numbers = {"logit_gap": float(max((g.max() for g in gaps),
                                      default=float("nan")))}
    # work of one wave, for the per-layer readers
    decode_rows = sum(m - 1 for _, m in shapes)
    live = sum((m - 1) * p + m * (m - 1) / 2 for p, m in shapes)
    record = {"waves": n_waves, "peaks": peaks, "chips": cell.chips,
              "config": config, "traffic": traffic, "events": prof.events,
              "decode_rows_per_wave": decode_rows,
              "live_tokens_per_wave": live,
              "weight_bytes": 4 if config["param_dtype"] == "float32" else 2}
    res = Result(attempted=attempted, failed=failed,
                 end_to_end={"serve_tokens_per_s": tokens / (t1 - t0),
                             "setup_s": setup_s},
                 record=record, numbers=numbers, limits=dict(cell.limits),
                 memory_peak_bytes=peak_bytes)
    if prof.events is not None:
        lo, hi = tr.window(prof.events, "cb.window")
        res.busy_s = tr.busy_s(prof.events, lo, hi)
        res.window_s = (hi - lo) / 1e9
        record["window"] = (lo, hi)
        res.breakdown = {"device_ops": tr.top_ops(prof.events, lo, hi),
                         "idle_gaps": tr.idle_gaps(prof.events, lo, hi)}
    print(f"serve: setup {setup_s:.3f} s, {n_waves} waves, {tokens} tokens "
          f"in {t1 - t0:.3f} s; engine counters since set-up {counters}; "
          f"sample gaps "
          f"{[round(float(g.max()), 5) for g in gaps]}",
          file=sys.stderr, flush=True)
    return res
