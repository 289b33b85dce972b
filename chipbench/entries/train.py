"""Train entry: the program's compiled train step, timed, then checked.

Set-up builds one object, the step ``train.loop.jit_train_step`` compiled
for the cell's batch with its TrainState made on the device from the seed,
and drives it through its first three steps with the window's own call
and feed.  The window then goes on with the same object: steps enqueued
back to back, each step's loss read after the next one is enqueued,
batches uploaded each step from a ring of distinct seeded batches.

After the window, with the program's state freed, the plain reference
(``reference/<config's reference>.py`` with ``reference/blockwise_adam.py``)
repeats the first three steps from the same seed, and three numbers are
compared with the cell's limits (``limits/<workload>.json``):

  loss    largest relative gap of the three steps' losses;
  grad    worst leaf's gap of the first gradient's norm as the optimizer
          got it, read from the first moment after step 1;
  update  worst leaf's gap of the norm of the parameters' change over
          the three steps, leaves whose reference gradient is under a
          thousandth of the median leaf's left out (Adam moves them by
          round-off alone).

A leaf's gap is |program norm - reference norm| over the larger of the
reference's norm of that leaf and of the median leaf.
"""
from __future__ import annotations

import gc
import importlib
import sys
import time

import numpy as np

from chipbench import gen, modelcfg
from chipbench import trace as tr
from chipbench.reference import blockwise_adam
from chipbench.result import Result, device_peak_bytes

CHECK_STEPS = 3
ZERO_GRAD_SHARE = 1e-3


def _ref_module(config):
    return importlib.import_module(f"chipbench.reference.{config['reference']}")


def _bits(traffic) -> int:
    """State width of the cell's optimizer; the reference models Adam and
    AdamW (decoupled weight decay) only."""
    name = traffic["optimizer"]
    if name not in ("adam8", "adam32", "adamw8", "adamw32"):
        raise NotImplementedError(f"no reference for optimizer {name!r}")
    return 8 if name.endswith("8") else 32


def leaf_gaps(prog, ref, keep=None) -> np.ndarray:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    keep = np.ones(ref.shape, bool) if keep is None else keep
    floor = np.maximum(ref, np.median(ref[keep]))
    return np.where(keep, np.abs(prog - ref) / floor, 0.0)


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers from two readings {losses, grad1, change3}."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    g_ref = np.asarray(ref["grad1"])
    keep = g_ref >= ZERO_GRAD_SHARE * np.median(g_ref)
    return {
        "loss": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad": float(np.max(leaf_gaps(prog["grad1"], g_ref))),
        "update": float(np.max(leaf_gaps(prog["change3"], ref["change3"],
                                         keep))),
    }


def reference_readings(config, traffic, key, ring, *, precision="f32",
                       fault=None) -> dict:
    """The reference's first three steps: losses, per-leaf norms of the
    first gradient as its optimizer got it, and of the change.
    ``precision="fp8"`` is the control; ``fault`` plants a fault:
    ``"half_batch"``, half of each batch left out, the mean taken over the
    rest; ``"r_neighbour_code"``, each stored 8-bit second-moment code
    read back as the next code up (the top code stays)."""
    import jax
    import jax.numpy as jnp
    ref = _ref_module(config)
    shapes = ref.param_shapes(config)
    paths = ref.leaf_paths(shapes)
    treedef = jax.tree_util.tree_structure(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    p0 = jax.jit(lambda k: ref.init_params(config, k))(key)
    p0 = dict(zip(paths, jax.tree_util.tree_leaves(p0)))
    kw = traffic["optimizer_kw"]
    opt = blockwise_adam.BlockwiseAdam(
        bits=_bits(traffic), lr=kw["lr"], beta1=kw["beta1"],
        beta2=kw["beta2"], eps=kw["eps"], weight_decay=kw["weight_decay"],
        grad_clip=traffic["grad_clip"])
    if fault == "r_neighbour_code":
        opt.qr_read = np.append(opt.qr[1:], opt.qr[-1])
    state = opt.init(p0)
    params = dict(p0)
    losses, grad1 = [], None
    with jax.default_matmul_precision("highest"):
        for t in range(1, CHECK_STEPS + 1):
            toks = ring[t - 1]
            if fault == "half_batch":
                toks = toks[: toks.shape[0] // 2]
            tree = jax.tree_util.tree_unflatten(
                treedef, [params[p] for p in paths])
            loss, grads = ref.loss_and_grads(
                config, tree, jnp.asarray(toks),
                rows_per_block=traffic["ref_rows"], precision=precision)
            g = dict(zip(paths, jax.tree_util.tree_leaves(grads)))
            del grads, tree
            params, state = opt.step(params, g, state, t)
            del g
            losses.append(float(loss))
            if t == 1:
                grad1 = [float(jnp.linalg.norm(opt.first_moment(
                    p, state[p], params[p].shape))) / (1 - kw["beta1"])
                    for p in paths]
    change = [float(jnp.linalg.norm(params[p] - p0[p])) for p in paths]
    return {"losses": losses, "grad1": grad1, "change3": change,
            "paths": paths}


def _program_readers(opt, config, ref, beta1):
    """Jitted readers of the program's state: per-leaf norms of the first
    moment / (1 - beta1), and of the change from the seeded weights."""
    import jax
    import jax.numpy as jnp
    from repro.core.optim import Full32Leaf, Quant8Leaf, unpool_state
    qm = blockwise_adam.dynamic_map(True)

    def is_leaf(x):
        return isinstance(x, (Quant8Leaf, Full32Leaf))

    @jax.jit
    def grad_norms(opt_state):
        out = []
        for leaf in jax.tree_util.tree_leaves(unpool_state(opt_state).leaves,
                                              is_leaf=is_leaf):
            if isinstance(leaf, Quant8Leaf):
                if not isinstance(leaf.codes_m, jax.Array) or \
                        leaf.codes_m.dtype != jnp.uint8:
                    raise NotImplementedError("only 8-bit codes are read")
                m = blockwise_adam.decode(leaf.codes_m, leaf.absmax_m, qm)
            else:
                m = leaf.m
            out.append(jnp.sqrt(jnp.sum(jnp.square(m))))
        return jnp.stack(out) / (1.0 - beta1)

    @jax.jit
    def change_norms(opt_state, key):
        now = jax.tree_util.tree_leaves(opt.params_view(opt_state,
                                                        jnp.float32))
        start = jax.tree_util.tree_leaves(ref.init_params(config, key))
        return jnp.stack([jnp.linalg.norm(a - b) for a, b in zip(now, start)])

    return grad_norms, change_norms


def build(cell, seed):
    """The program's optimizer, compiled step and state, and the ring."""
    import jax
    import jax.numpy as jnp
    from repro.core.optim import make_optimizer
    from repro.models import model as M
    from repro.telemetry import tracing
    from repro.train import loop as TL

    config, traffic = cell.config, cell.traffic
    _bits(traffic)                     # refuse an optimizer with no reference
    mcfg = modelcfg.program_config(config)
    ref = _ref_module(config)
    want = jax.tree_util.tree_map(
        lambda s: tuple(s.shape),
        jax.eval_shape(lambda: M.init_model(mcfg, jax.random.PRNGKey(0))[0]))
    if want != ref.param_shapes(config):
        raise ValueError("the program's parameter tree differs from the "
                         "reference's")
    # named scopes (tel.forward_backward / tel.optimizer_update) in the
    # compiled step's metadata: the trace's ops are attributed by them
    tracing.set_phase_tracing(True)
    opt = make_optimizer(traffic["optimizer"], **traffic["optimizer_kw"])
    key = ref.make_key(seed)
    state = jax.jit(lambda k: TL.TrainState(
        opt_state=opt.init(ref.init_params(config, k)),
        step=jnp.zeros((), jnp.int32)))(key)
    ring = gen.train_ring(traffic, config["vocab_size"], seed)
    step = TL.jit_train_step(
        mcfg, opt, TL.TrainHyper(grad_clip=traffic["grad_clip"]))
    compiled = step.lower(state, {"tokens": ring[0]}).compile()
    return opt, compiled, state, ring, key


def first_steps(cell, seed):
    """Set-up: build, then the first steps through the window's own call
    and feed.  Returns (program readings, what the window goes on with)."""
    import jax
    config, traffic = cell.config, cell.traffic
    opt, step, state, ring, key = build(cell, seed)
    grad_norms, change_norms = _program_readers(
        opt, config, _ref_module(config), traffic["optimizer_kw"]["beta1"])

    def feed(i):
        with tr.span("batch_upload"):
            return {"tokens": jax.device_put(ring[i % len(ring)])}

    losses, grad1 = [], None
    for i in range(CHECK_STEPS):
        state, mx = step(state, feed(i))
        losses.append(mx["loss"])
        if i == 0:
            grad1 = grad_norms(state.opt_state)
    change3 = change_norms(state.opt_state, key)
    prog = {"losses": [float(x) for x in losses],
            "grad1": np.asarray(grad1).tolist(),
            "change3": np.asarray(change3).tolist()}
    return prog, dict(opt=opt, step=step, state=state, ring=ring, key=key,
                      feed=feed)


def run(cell, *, seed, seconds, trace, t_start, peaks):
    import jax
    config, traffic = cell.config, cell.traffic
    prog, live = first_steps(cell, seed)
    step, state, feed = live["step"], live["state"], live["feed"]
    key, ring = live["key"], live["ring"]
    ma = step.memory_analysis()
    hbm = (ma.argument_size_in_bytes + ma.output_size_in_bytes
           - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    setup_s = time.perf_counter() - t_start
    loads = tr.ProgramLoads()

    # the window
    tokens_per_step = traffic["global_batch"] * traffic["seq_len"]
    n, failed, prev = 0, 0, None
    with tr.Profiled(trace) as prof:
        t0 = time.perf_counter()
        while True:
            with tr.span("step_enqueue"):
                state, mx = step(state, feed(CHECK_STEPS + n))
            n += 1
            if prev is not None:
                with tr.span("loss_read"):
                    failed += not np.isfinite(float(prev))
            prev = mx["loss"]
            elapsed = time.perf_counter() - t0
            if (trace and n >= traffic["trace_steps"]) or \
                    (not trace and elapsed >= seconds):
                break
        with tr.span("drain"):
            jax.block_until_ready(state)
            failed += not np.isfinite(float(prev))
        t1 = time.perf_counter()
    peak_bytes = device_peak_bytes()
    print(f"train: {loads.n} programs compiled or loaded in the window",
          file=sys.stderr, flush=True)
    print(f"train: step program {hbm} B by the compiler's memory analysis "
          f"(arguments {ma.argument_size_in_bytes}, temporaries "
          f"{ma.temp_size_in_bytes}); device peak in use + reserved "
          f"{peak_bytes} B", file=sys.stderr, flush=True)
    scopes = tr.scope_map(step.as_text()) if prof.events else {}
    del state, step, mx, prev, live
    gc.collect()

    refr = reference_readings(config, traffic, key, ring)
    numbers = compare(prog, refr)
    record = {"steps": n, "tokens_per_step": tokens_per_step, "peaks": peaks,
              "chips": cell.chips, "config": config, "traffic": traffic,
              "events": prof.events, "scopes": scopes}
    res = Result(attempted=n, failed=failed,
                 end_to_end={"train_tokens_per_s":
                             n * tokens_per_step / (t1 - t0),
                             "train_hbm_gib": peak_bytes / 2 ** 30,
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
    print(f"train: setup {setup_s:.3f} s, {n} steps in {t1 - t0:.3f} s, "
          f"losses {prog['losses']} vs reference {refr['losses']}",
          file=sys.stderr, flush=True)
    return res
