"""Optimizer-exposed step time: sequential ZeRO-1 vs the overlapped
ZeRO-2 path (DESIGN.md §13).

The sequential PR-5 step exposes the whole optimizer phase after the last
microbatch's backward: clip over the replicated grad pytree, the
flatten/concat into the arena layout, the reduce-scatter, one owned-span
fused update per device, and the eager params all-gather.  The overlapped
step moves everything but the tail off the critical path: grads flatten
and reduce-scatter bucket-by-bucket *inside* the accumulation loop
(``OptimConfig.shard_grads`` + ``overlap_buckets``), the per-bucket
updates are mutually independent dispatches that fire as their grads
land, and the params all-gather is deferred to the next step's first use
(``materialize_params=False``).  What stays exposed is the finalization
tail: the buffer-clip reduction plus the LAST bucket's owned-span update.

This bench measures both legs on a 4-device host mesh:

  * ``opt_exposed_ms/sequential`` — wall-clock of the full sequential
    phase (clip + apply + params view) from replicated grads;
  * ``opt_exposed_ms/overlap`` — wall-clock of the buffer-clip plus a
    tail-bucket-sized apply (the same machinery at 1/K of the rows —
    the one dispatch that cannot be hidden), deferred params.

and the static ZeRO-2 peak-gradient accounting
(``grad_buffer_bytes``): owned-span share vs the replicated pytree.
Gates: overlap exposed <= 0.5x sequential, 4-way sharded grad bytes
<= 0.35x replicated.  Bit-exactness of the overlapped path vs the
sequential oracle is asserted here end-to-end and proven leaf-by-leaf in
tests/test_overlap.py.  Appends to BENCH_speed.json.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import append_bench_json, emit, time_fn
from benchmarks.bench_speed import BENCH_JSON
from repro.core.optim import make_optimizer
from repro.train import loop as L
from repro.launch.mesh import make_mesh

SHARDS = 4
BUCKETS = 4


def _model(rows, cols, n_leaves):
    key = jax.random.PRNGKey(0)
    params = {f"layer{i:02d}": jax.random.normal(
        jax.random.fold_in(key, i), (rows, cols)) for i in range(n_leaves)}
    grads = jax.tree_util.tree_map(lambda p: p * 0.01, params)
    return params, grads


def _opt(mesh, **kw):
    return make_optimizer("adam8", lr=1e-3, min_8bit_size=256,
                          override_32bit=lambda p: False, mesh=mesh,
                          partition=True, partition_shards=SHARDS, **kw)


def bench_step_overlap(smoke: bool = False):
    if jax.device_count() < SHARDS:
        emit("step_overlap/SKIP", 0.0,
             f"needs {SHARDS} devices "
             f"(XLA_FLAGS=--xla_force_host_platform_device_count=4)")
        return None
    mesh = make_mesh((SHARDS,), ("data",))
    rows, cols, n_leaves = (32, 1024, 12) if smoke else (64, 1024, 12)
    iters = 5 if smoke else 10

    # --- sequential PR-5 leg: replicated grads -> clip -> apply (eager
    # params view): the whole phase is exposed after backward.
    params, grads = _model(rows, cols, n_leaves)
    opt_s = _opt(mesh)
    st = opt_s.init(params)

    def seq(g, s):
        g, _ = L.clip_by_global_norm(g, 1.0)
        return opt_s.apply(g, s)

    seq_ms, (seq_params, seq_state) = time_fn(jax.jit(seq), grads, st,
                                              iters=iters, warmup=2)
    seq_ms /= 1e3   # time_fn returns us

    # --- overlapped leg, exposed tail only.  (a) the buffer-clip
    # finalization: global norm off the owned-span buffer + scale.
    opt_o = _opt(mesh, shard_grads=True, overlap_buckets=BUCKETS)
    st_o = opt_o.init(params)
    buf = opt_o.accumulate_grads(opt_o.init_grad_buffer(st_o), grads)

    def bclip(b):
        gn = opt_o.grad_buffer_norm(b)
        scale = jnp.minimum(1.0, 1.0 / jnp.maximum(gn, 1e-12))
        return jax.tree_util.tree_map(lambda x: x * scale, b)

    clip_ms, _ = time_fn(jax.jit(bclip), buf, iters=iters, warmup=2)
    clip_ms /= 1e3

    # (b) the last bucket's owned-span update: the K per-bucket dispatches
    # are mutually independent (disjoint static slices — see
    # tests/test_overlap.py), so buckets 0..K-2 fire behind the still-
    # arriving grads and only the final one is on the critical path.
    # Measured as a real end-to-end apply of the same leaf structure at
    # 1/K of the rows, deferred params (no all-gather on the tail).
    params_t, grads_t = _model(rows // BUCKETS, cols, n_leaves)
    opt_t = _opt(mesh, shard_grads=True)
    st_t = opt_t.init(params_t)
    buf_t = opt_t.accumulate_grads(opt_t.init_grad_buffer(st_t), grads_t)
    tail_ms, _ = time_fn(
        jax.jit(lambda b, s: opt_t.apply(b, s, materialize_params=False)[1]),
        buf_t, st_t, iters=iters, warmup=2)
    tail_ms /= 1e3

    ov_ms = clip_ms + tail_ms
    ratio = ov_ms / max(seq_ms, 1e-9)
    emit("step_overlap/sequential/opt_exposed_ms", seq_ms * 1e3,
         f"clip+apply+gather, {SHARDS}-dev mesh")
    emit("step_overlap/overlap/opt_exposed_ms", ov_ms * 1e3,
         f"bufclip {clip_ms:.2f}ms + tail bucket {tail_ms:.2f}ms "
         f"(K={BUCKETS}), {ratio:.3f}x of sequential")
    assert ratio <= 0.5, (
        f"overlapped exposed {ov_ms:.2f}ms > 0.5x sequential {seq_ms:.2f}ms")

    # --- ZeRO-2 peak grad bytes (static accounting, DESIGN.md §13b)
    gbb = opt_o.grad_buffer_bytes(st_o)
    frac = gbb["sharded_grad_bytes"] / max(gbb["replicated_grad_bytes"], 1)
    emit("step_overlap/peak_grad_bytes", float(gbb["sharded_grad_bytes"]),
         f"{frac:.3f}x of replicated ({gbb['replicated_grad_bytes']}B), "
         f"{SHARDS} shards")
    assert frac <= 0.35, gbb

    # --- bit-exactness: overlapped path == sequential oracle end-to-end
    def ov_full(b, s):
        gn = opt_o.grad_buffer_norm(b)
        scale = jnp.minimum(1.0, 1.0 / jnp.maximum(gn, 1e-12))
        b = jax.tree_util.tree_map(lambda x: x * scale, b)
        return opt_o.apply(b, s, materialize_params=False)[1]

    ov_state = jax.jit(ov_full)(buf, st_o)
    for a, b in zip(jax.tree_util.tree_leaves(seq_params),
                    jax.tree_util.tree_leaves(
                        opt_o.params_view(ov_state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    emit("step_overlap/bit_exact", 0.0, "overlap == sequential oracle")

    entry = {
        "bench": "step_overlap",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "smoke": smoke, "backend": jax.default_backend(),
        "devices": SHARDS, "overlap_buckets": BUCKETS,
        "n_leaves": n_leaves,
        "opt_exposed_ms": {"sequential": seq_ms, "overlap": ov_ms,
                           "buffer_clip": clip_ms, "tail_bucket": tail_ms},
        "exposed_ratio": ratio,
        "peak_grad_bytes": gbb["sharded_grad_bytes"],
        "replicated_grad_bytes": gbb["replicated_grad_bytes"],
        "peak_grad_fraction": frac,
    }
    path = append_bench_json(BENCH_JSON, entry)
    emit("step_overlap/json", 0.0, path)
    return entry


def main(smoke: bool = False):
    bench_step_overlap(smoke=smoke)


if __name__ == "__main__":
    main()
