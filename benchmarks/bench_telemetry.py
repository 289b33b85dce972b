"""Telemetry smoke leg (DESIGN.md §14): the observable 8-bit stack
end-to-end.

Ten optimizer steps of ``muon8`` — matrix leaves per-leaf (Newton–Schulz
momentum) plus 1-D leaves pooled in the QuantArena, ZeRO-1 partitioned
when 4 host devices are forced — with phase tracing on, qhealth probes
every 2 steps, and the registry routed to a JSONL sink.  The artifact is
then schema-validated (``repro.telemetry.validate_jsonl``) and must
contain:

  * "qhealth" events for BOTH the pooled arena (``target="arena"``) and
    a muon matrix leaf (``target="leaf"``), each with a saturation
    fraction, a 256-bin codebook-utilization histogram, and absmax drift;
  * one "trace" event carrying the per-phase fused-dispatch accounting of
    the compiled step;
  * per-step "phase" timeline events and registry "metric" events.

Appends a summary entry to BENCH_speed.json.  This is the CI
``--telemetry`` leg (scripts/ci.sh runs it on the forced 4-device host
mesh; on fewer devices it degrades to the unpartitioned single-device
run, which validates the same schema).
"""
from __future__ import annotations

import os
import tempfile
import time

import jax
import jax.numpy as jnp

from benchmarks.bench_speed import BENCH_JSON
from benchmarks.common import append_bench_json, emit
from repro import telemetry as tel
from repro.core.optim import make_optimizer
from repro.telemetry import tracing
from repro.launch.mesh import make_mesh

STEPS = 10
EVERY = 2
SHARDS = 4


def bench_telemetry_jsonl(smoke: bool = False):
    shards = SHARDS if jax.device_count() >= SHARDS else 1
    mesh = make_mesh((shards,), ("data",)) if shards > 1 else None
    key = jax.random.PRNGKey(0)
    n_mat, n_vec = 2, 6
    params = {f"w{i}": jax.random.normal(jax.random.fold_in(key, i),
                                         (32, 64)) for i in range(n_mat)}
    params.update({f"v{i}": jax.random.normal(
        jax.random.fold_in(key, 100 + i), (1024,)) for i in range(n_vec)})
    kw = ({"partition": True, "partition_shards": shards, "mesh": mesh}
          if mesh is not None else {})
    opt = make_optimizer("muon8", lr=1e-2, min_8bit_size=256,
                         override_32bit=lambda p: False,
                         telemetry_every=EVERY, **kw)

    # BENCH_TELEMETRY_DIR pins the artifact dir so a later CI leg can
    # point the run inspector at it (scripts/ci.sh, DESIGN.md §16)
    out_dir = os.environ.get("BENCH_TELEMETRY_DIR")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    else:
        out_dir = tempfile.mkdtemp(prefix="bench_telemetry_")
    path = os.path.join(out_dir, "telemetry.jsonl")
    if os.path.exists(path):
        os.remove(path)            # JsonlSink appends; start fresh
    reg = tel.MetricRegistry()
    reg.add_sink(tel.JsonlSink(path))
    tracing.set_phase_tracing(True)   # before tracing: scopes bake in
    tracing.reset_trace_events()
    try:
        state = opt.init(params)
        probe = tel.QHealthProbe(opt, mesh=mesh)
        step = jax.jit(lambda g, s: opt.apply(g, s))
        timer = tracing.StepTimer()
        pv = params
        for i in range(STEPS):
            with timer.step():
                grads = jax.tree_util.tree_map(
                    lambda p: p * (0.01 + 0.001 * i), pv)
                pv, state = step(grads, state)
                jax.block_until_ready(jax.tree_util.tree_leaves(pv)[0])
            if i == 0:
                reg.emit_event(tracing.trace_event_dict(i))
                tracing.reset_trace_events()
            reg.emit_event({"kind": "phase", "step": i, "phase": "step",
                            "wall_s": timer.last_dt})
            reg.record_scalars(
                i, {"p0_norm": jnp.linalg.norm(
                    jax.tree_util.tree_leaves(pv)[0])}, prefix="opt/")
            if (i + 1) % EVERY == 0:
                with tracing.host_phase("qhealth_probe", step=i):
                    for ev in probe.probe(state, step=i):
                        reg.emit_event(ev)
                for ev in tracing.drain_phase_events():
                    reg.emit_event(ev)
        reg.gauge("opt/steady_ms").set(timer.steady_ms())
        reg.flush(step=STEPS - 1)
        reg.close()
    finally:
        tracing.set_phase_tracing(False)

    events, errors = tel.validate_jsonl(path)
    assert not errors, errors[:5]
    kinds = sorted({e["kind"] for e in events})
    assert {"metric", "phase", "qhealth", "trace"} <= set(kinds), kinds
    q = [e for e in events if e["kind"] == "qhealth"]
    targets = {e["target"] for e in q}
    assert targets == {"arena", "leaf"}, targets
    for e in q:
        assert 0.0 <= e["saturation_fraction"] <= 1.0, e
        assert len(e["util_hist"]) == e["n_bins"] == 256, e
        assert e["absmax_drift"] > 0.0, e
    tr = next(e for e in events if e["kind"] == "trace")
    assert any(p["dispatches"] > 0 for p in tr["phases"]), tr
    n_probe = len([e for e in events if e["kind"] == "phase"
                   and e["phase"] == "qhealth_probe"])
    assert n_probe == STEPS // EVERY, n_probe
    emit("telemetry/jsonl_events", float(len(events)),
         f"{len(q)} qhealth over {len({e['segment'] for e in q})} segments, "
         f"{shards}-device, schema-valid")
    entry = {
        "bench": "telemetry_jsonl", "algo": "muon",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "smoke": smoke, "backend": jax.default_backend(),
        "devices": shards, "steps": STEPS, "telemetry_every": EVERY,
        "n_events": len(events), "n_qhealth": len(q),
        "qhealth_targets": sorted(targets), "event_kinds": kinds,
    }
    p = append_bench_json(BENCH_JSON, entry)
    emit("telemetry/json", 0.0, p)
    return entry


def main(smoke: bool = False):
    bench_telemetry_jsonl(smoke=smoke)


if __name__ == "__main__":
    main()
