"""Training step construction: loss, grad accumulation, clipping, optimizer.

``make_train_step`` builds the jit-able function the launcher lowers for the
multi-pod dry-run; ``train_loop`` is the host loop used by the examples and
the end-to-end driver (checkpointing, preemption, straggler logging live in
repro/launch/train.py).
"""
from __future__ import annotations

import dataclasses
import inspect
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.analysis import contracts as _contracts
from repro.models import model as M
from repro.telemetry import tracing as _tracing

Pytree = Any


class TrainState(NamedTuple):
    """params are NOT stored: they are a cast view of the optimizer's
    (sharded, flat-block) master copies, re-materialized inside each step —
    ZeRO-3 style, no persistent model-shape duplicate.

    ``opt_state`` also carries the optimizer's auxiliary state: the
    percentile-clipping gnorm history (``OptState.gnorm_vec``) rides here
    and therefore checkpoints/restores with everything else, as do the
    pooled-dispatch arenas (``OptState.arena`` / ``pool32``, DESIGN.md
    §10 — checkpointed per-leaf, so pooled and per-leaf runs share
    checkpoints); stochastic-rounding seeds are derived from
    ``opt_state.step`` inside the optimizer, so a restore replays
    identical rounding — no RNG state to persist."""
    opt_state: Any            # optimizer-owned (master, 8-bit stats, gnorms)
    step: jax.Array           # int32


@dataclasses.dataclass(frozen=True)
class TrainHyper:
    grad_clip: float = 1.0
    microbatches: int = 1
    label_smoothing: float = 0.0
    moe_aux_coef: float = 0.01
    moe_z_coef: float = 1e-3
    lr_schedule: Optional[Callable[[jax.Array], jax.Array]] = None


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  smoothing: float = 0.0) -> jax.Array:
    """Mean token NLL in f32. logits (B, S, V), labels (B, S).

    The gold logit is extracted with a vocab-local masked reduction (not
    take_along_axis) so the loss works on *vocab-sharded* logits without an
    all-gather — with V=100k+ and f32 logits that gather is a 100GB+
    catastrophe the roofline caught (EXPERIMENTS.md §Perf)."""
    from repro.models.constrain import constrain
    logits = constrain(logits.astype(jnp.float32), "dp", None, "tp")
    logz = jax.nn.logsumexp(logits, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    gold = jnp.sum(jnp.where(vocab_iota == labels[..., None], logits, 0.0),
                   axis=-1)
    nll = logz - gold
    if smoothing > 0.0:
        mean_lp = jnp.mean(logits - logz[..., None], axis=-1)
        nll = (1 - smoothing) * nll - smoothing * mean_lp
    return jnp.mean(nll)


def global_norm(tree: Pytree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree_util.tree_leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def clip_by_global_norm(tree: Pytree, max_norm: float):
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree_util.tree_map(lambda x: x * scale, tree), norm


def make_loss_fn(cfg, hyper: TrainHyper):
    def loss_fn(params, batch):
        tokens = batch["tokens"]
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
        embeds = batch.get("embeds")
        logits, mx = M.forward(cfg, params, inputs, embeds=embeds)
        if embeds is not None:
            logits = logits[:, -labels.shape[1]:]   # loss on token positions
        loss = cross_entropy(logits, labels, hyper.label_smoothing)
        total = loss
        if "moe_aux_loss" in mx:
            total = total + hyper.moe_aux_coef * mx["moe_aux_loss"] \
                          + hyper.moe_z_coef * mx["moe_z_loss"]
        mx = dict(mx)
        mx["ce_loss"] = loss
        return total, mx
    return loss_fn


def make_train_step(cfg, optimizer, hyper: TrainHyper = TrainHyper(),
                    param_shardings=None):
    """Returns train_step(state, batch) -> (state, metrics).

    grad accumulation: batch is split into ``hyper.microbatches`` equal
    slices along the batch dim and grads averaged with a scan (bounds
    activation + MoE dispatch memory — the per-(arch,shape) knob of §Perf).

    ``param_shardings``: optional pytree of NamedSharding constraining the
    params view reconstructed from the flat-block master — without it XLA
    propagates the block-domain sharding through the reshape and lands on
    the scan (layers) dim, triggering involuntary full rematerialization.
    """
    loss_fn = make_loss_fn(cfg, hyper)
    param_dtype = jnp.dtype(cfg.param_dtype)
    # ZeRO-2 (DESIGN.md §13): accumulate straight into the optimizer's
    # owned-span GradBuffer instead of a replicated param-shaped pytree.
    shard_grads = bool(
        getattr(getattr(optimizer, "cfg", None), "shard_grads_active", False)
        and hasattr(optimizer, "init_grad_buffer"))
    # Deferred all-gather (§13d): apply() skips the model-shape params
    # reconstruction when it supports the kwarg — train_step discards that
    # output anyway; params re-materialize at their first use, the
    # params_view call at the top of the NEXT step.
    defer_kw = {}
    if "materialize_params" in inspect.signature(
            optimizer.apply).parameters:
        defer_kw["materialize_params"] = False
    # Numerics sentinel (DESIGN.md §16): static — when on, apply() returns
    # (params, state, health) and the step surfaces the HealthFlags counts
    # as sent_* metrics; when off the step lowers byte-identically to a
    # sentinel-free build (the train_step.sentinel_invariant contract).
    sentinel_on = bool(getattr(getattr(optimizer, "cfg", None),
                               "sentinel", False))

    def compute_grads(params, batch):
        if hyper.microbatches <= 1:
            (loss, mx), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
            return loss, mx, grads

        n = hyper.microbatches

        def micro(carry, mb):
            acc, loss_acc = carry
            (loss, mx), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
            acc = jax.tree_util.tree_map(jnp.add, acc, g)
            return (acc, loss_acc + loss), mx

        def split(x):
            b = x.shape[0]
            return x.reshape(n, b // n, *x.shape[1:])

        mbs = jax.tree_util.tree_map(split, batch)
        # The accumulator MUST carry the param sharding: an unconstrained
        # zeros tree lets SPMD replicate it, turning every microbatch's
        # gradient into a full (unsharded) all-reduce — measured as ~90x
        # param-bytes of all-reduce on kimi train_4k (§Perf A3).
        zero = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        if param_shardings is not None:
            zero = jax.tree_util.tree_map(
                jax.lax.with_sharding_constraint, zero, param_shardings)
        (gsum, loss_sum), mxs = jax.lax.scan(micro, (zero, 0.0), mbs)
        grads = jax.tree_util.tree_map(lambda g: g / n, gsum)
        mx = {k: jnp.mean(v) for k, v in mxs.items()}
        return loss_sum / n, mx, grads

    def compute_grad_buffer(params, batch, opt_state):
        """ZeRO-2 accumulation (DESIGN.md §13): each microbatch's grads
        flatten into the owned-span GradBuffer bucket-by-bucket as they
        are produced — the replicated grad pytree never outlives one
        microbatch, and each bucket's reduce-scatter overlaps the next
        microbatch's backward.  Addition commutes with the (exact)
        flatten, so the accumulated values are bit-identical to the
        param-shaped accumulator above."""
        buf0 = optimizer.init_grad_buffer(opt_state)
        if hyper.microbatches <= 1:
            (loss, mx), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return loss, mx, optimizer.accumulate_grads(buf0, grads)

        n = hyper.microbatches

        def micro(carry, mb):
            buf, loss_acc = carry
            (loss, mx), g = jax.value_and_grad(loss_fn, has_aux=True)(params,
                                                                      mb)
            return (optimizer.accumulate_grads(buf, g), loss_acc + loss), mx

        def split(x):
            b = x.shape[0]
            return x.reshape(n, b // n, *x.shape[1:])

        mbs = jax.tree_util.tree_map(split, batch)
        (buf, loss_sum), mxs = jax.lax.scan(micro, (buf0, 0.0), mbs)
        buf = jax.tree_util.tree_map(lambda g: g / n, buf)
        mx = {k: jnp.mean(v) for k, v in mxs.items()}
        return loss_sum / n, mx, buf

    def train_step(state: TrainState, batch):
        params = optimizer.params_view(state.opt_state, param_dtype)
        if param_shardings is not None:
            params = jax.tree_util.tree_map(
                jax.lax.with_sharding_constraint, params, param_shardings)
        with _tracing.annotate("forward_backward"):
            if shard_grads:
                loss, mx, grads = compute_grad_buffer(params, batch,
                                                      state.opt_state)
                # same clip formula as clip_by_global_norm, with the norm
                # taken from the buffer (bit-identical per-leaf reductions)
                gnorm = optimizer.grad_buffer_norm(grads)
                scale = jnp.minimum(1.0, hyper.grad_clip /
                                    jnp.maximum(gnorm, 1e-12))
                grads = jax.tree_util.tree_map(lambda x: x * scale, grads)
            else:
                loss, mx, grads = compute_grads(params, batch)
                grads, gnorm = clip_by_global_norm(grads, hyper.grad_clip)
        lr = hyper.lr_schedule(state.step) if hyper.lr_schedule else None
        from repro.kernels import ops as kops
        dispatch0 = kops.fused_update_count()
        with _tracing.annotate("optimizer_update"):
            out = optimizer.apply(grads, state.opt_state, lr=lr,
                                  param_dtype=param_dtype, **defer_kw)
        health = None
        if sentinel_on:
            _, new_opt, health = out
        else:
            _, new_opt = out
        metrics = {"loss": loss, "grad_norm": gnorm, **mx}
        if health is not None:
            from repro.kernels import fused_update as kfu
            for i, nm in enumerate(kfu.HEALTH_SLOTS):
                metrics[f"sent_{nm}"] = health[i]
        # Counted at trace time => a constant under jit: how many fused
        # optimizer dispatches the compiled step bakes in.  1 per state-
        # format arena with the pooled dispatch (DESIGN.md §10), O(#leaves)
        # per-leaf, 0 for 32-bit engines.
        metrics["opt_fused_dispatches"] = jnp.float32(
            kops.fused_update_count() - dispatch0)
        if hasattr(optimizer, "state_bytes"):
            # Static-shape accounting (constant under jit): the *measured*
            # optimizer-statistics bytes per parameter, so k-bit memory
            # savings are observable in the metrics stream, not inferred
            # from the config (DESIGN.md §9).
            sb = optimizer.state_bytes(state.opt_state)
            if sb.get("n_params"):
                metrics["state_bytes_per_param"] = jnp.float32(
                    sb["state_bytes"] / sb["n_params"])
            if "owned_state_bytes" in sb:
                # Partitioned (ZeRO-1) dispatch (DESIGN.md §12): the
                # largest owner's block span and its share of the
                # statistics — what one device actually holds/updates.
                metrics["opt_owned_blocks"] = jnp.float32(
                    sb["owned_blocks"])
                metrics["opt_owned_state_bytes_per_param"] = jnp.float32(
                    sb["owned_state_bytes"] / sb["n_params"])
        if shard_grads and hasattr(optimizer, "grad_buffer_bytes"):
            # ZeRO-2 accounting (DESIGN.md §13): what one device holds of
            # the accumulated grads vs the replicated pytree it replaces.
            gbb = optimizer.grad_buffer_bytes(state.opt_state)
            metrics["peak_grad_bytes"] = jnp.float32(
                gbb["sharded_grad_bytes"])
            metrics["replicated_grad_bytes"] = jnp.float32(
                gbb["replicated_grad_bytes"])
        if getattr(optimizer, "cfg", None) is not None and \
                getattr(optimizer.cfg, "percentile_clipping", 100) < 100:
            # Same subgraph apply() evaluates internally -> CSE'd by XLA;
            # surfaces how hard percentile clipping bit this step.
            scale, _ = optimizer.percentile_clip(grads, state.opt_state)
            metrics["pclip_scale"] = scale
        return TrainState(opt_state=new_opt, step=state.step + 1), metrics

    return train_step


def jit_train_step(cfg, optimizer, hyper: TrainHyper = TrainHyper(),
                   param_shardings=None, *, donate: bool = True,
                   **jit_kwargs):
    """``jax.jit(make_train_step(...))`` with the TrainState donated
    (DESIGN.md §13c): the optimizer state's codes/absmax/masters alias
    their output buffers in place instead of round-tripping HBM twice.
    Callers must rebind ``state`` each step (every in-repo loop does);
    pass ``donate=False`` to keep the old state alive (A/B comparisons).
    """
    step = make_train_step(cfg, optimizer, hyper, param_shardings)
    donate_argnums = (0,) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums, **jit_kwargs)


def donation_aliases(lowered) -> int:
    """Number of donated-input/output buffer aliasings a ``.lower()``-ed
    step actually established (the ``tf.aliasing_output`` markers in the
    StableHLO) — the donation-aliasing audit hook (DESIGN.md §13c), now
    delegating to the contract checker (``repro.analysis.contracts``)."""
    return _contracts.donation_aliases(lowered.as_text())


# ------------------------------------------------- compile contracts (§15)
# Registered here, next to the step construction they protect; evaluated
# over the config matrix by `python -m repro.analysis` (analysis/runner.py).

def _telemetry_invariant(pair, cell):
    """telemetry_every is host-schedule only (§14): every knob value must
    lower the step to byte-identical StableHLO, with no tel.* scope names
    leaking into the default trace."""
    ok, detail = _contracts.lowering_invariant(
        {k: low.text for k, low in pair.items()})
    if ok and any("tel." in low.text for low in pair.values()):
        return False, "tel.* scope names leaked into the default lowering"
    return ok, detail


_contracts.register(
    "train_step.donates", "step",
    lambda low, cell: _contracts.check_donates(low.text, min_aliases=1),
    doc="donated TrainState marks >=1 in-place alias/donor (§13c)")
_contracts.register(
    "train_step.no_f64", "step",
    lambda low, cell: _contracts.check_no_dtype(low.text, "f64"),
    doc="no f64 anywhere in the jitted step (§6 master-dtype policy)")
_contracts.register(
    "train_step.collective_order", "step",
    # markers: grads pinned into the owned-span layout -> reduce-scatter
    # boundary (span entry) -> all-gather boundary (span exit)
    lambda low, cell: (_contracts.check_collective_order(
        low.text, *_contracts.SPMD_BOUNDARY_MARKERS)
        if getattr(cell, "shard_grads", False) else None),
    doc="ZeRO-2 step shape (§13): grad scatter pin -> span-local fused "
        "update (shard_map body) -> span exit; the implicit collectives "
        "ride these SPMD boundaries, so their order IS the "
        "reduce_scatter -> fused_update -> all_gather order")
_contracts.register(
    "train_step.telemetry_invariant", "pair:telemetry", _telemetry_invariant,
    doc="telemetry_every 0 vs N lower byte-identically (§14, ex-PR-7 test)")
_contracts.register(
    "train_step.overlap_donation_invariant", "pair:overlap",
    lambda pair, cell: _contracts.lowering_invariant(
        {k: low.text for k, low in pair.items()}, compare_aliases_only=True),
    doc="overlap_buckets 1 vs K restructures dispatch but must never cost "
        "a donated in-place arena (§13c)")


def _sentinel_invariant(pair, cell):
    """Sentinel zero-overhead contract (§16): the off default and an
    explicit sentinel=False must lower to byte-identical StableHLO (the
    feature costs nothing when off), and turning it on may only add the
    health outputs — the donated in-place arena aliasing set is
    unchanged."""
    off = {k: low.text for k, low in pair.items() if k != "on"}
    ok, detail = _contracts.lowering_invariant(off)
    if not ok:
        return False, f"sentinel-off not byte-identical: {detail}"
    return _contracts.lowering_invariant(
        {k: low.text for k, low in pair.items()}, compare_aliases_only=True)


_contracts.register(
    "train_step.sentinel_invariant", "pair:sentinel", _sentinel_invariant,
    doc="sentinel off lowers byte-identically; on preserves the donation "
        "aliasing set (§16)")


def init_train_state(cfg, optimizer, key) -> tuple[TrainState, Pytree]:
    """-> (state, logical param specs)."""
    params, specs = M.init_model(cfg, key)
    opt_state = optimizer.init(params)
    return TrainState(opt_state=opt_state,
                      step=jnp.zeros((), jnp.int32)), specs


def warmup_cosine(lr: float, warmup: int, total: int, floor: float = 0.1):
    def sched(step):
        step = step.astype(jnp.float32)
        warm = lr * jnp.minimum(1.0, (step + 1) / max(warmup, 1))
        frac = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * frac))
        return jnp.where(step < warmup, warm, lr * cos)
    return sched
