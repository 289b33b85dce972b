"""End-to-end training driver (deliverable b): config-driven, fault-tolerant.

  PYTHONPATH=src python -m repro.launch.train --arch paper-lm-209m \
      --optimizer adam8 --steps 300 --seq-len 128 --batch 16 \
      --ckpt-dir artifacts/run1 --out artifacts/run1/metrics.jsonl

Fault tolerance: resumes from the latest checkpoint in --ckpt-dir
automatically; SIGTERM/SIGINT triggers checkpoint-and-exit (preemption
handling); per-step wall times are z-score-monitored and logged as straggler
warnings (on multi-host this feeds the restart policy).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import base as cfgs
from repro.core.optim import make_optimizer
from repro.data.pipeline import DataConfig, SyntheticLMPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro import telemetry as tel
from repro.telemetry import tracing
from repro.train import checkpoint as ckpt
from repro.train import loop as train_loop


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-lm-209m")
    ap.add_argument("--optimizer", default="adam8")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--qmap", default="dynamic")
    ap.add_argument("--state-bits", default=None,
                    help="per-slot storage bitwidth for quantized states: "
                         "'4' or '4,8' (m,r); default 8-bit (DESIGN.md §9)")
    ap.add_argument("--no-blockwise", action="store_true")
    ap.add_argument("--no-stable-embedding", action="store_true")
    ap.add_argument("--no-32bit-embed-override", action="store_true")
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--n-layers", type=int, default=None)
    ap.add_argument("--vocab", type=int, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--out", default=None, help="metrics JSONL path")
    ap.add_argument("--shard-grads", action="store_true",
                    help="ZeRO-2: accumulate grads owned-span sharded "
                         "(DESIGN.md §13)")
    ap.add_argument("--overlap-buckets", type=int, default=1,
                    help="subdivide the partitioned arena update into N "
                         "buckets overlapping the reduce-scatter "
                         "(DESIGN.md §13)")
    ap.add_argument("--telemetry-dir", default=None,
                    help="emit telemetry JSONL (metrics, step phases, "
                         "qhealth probes) into this directory "
                         "(DESIGN.md §14)")
    ap.add_argument("--telemetry-every", type=int, default=0,
                    help="run quantization-health probes every N steps "
                         "(0 = off; requires --telemetry-dir)")
    ap.add_argument("--sentinel", action="store_true",
                    help="in-graph numerics sentinel: kernels count "
                         "nonfinite/overflow/saturation per dispatch and "
                         "host detectors escalate anomalies (DESIGN.md §16)")
    ap.add_argument("--flight-dir", default=None,
                    help="flight-recorder dump directory: on a fatal "
                         "anomaly or nonfinite loss, dump the metrics "
                         "ring + last healthy state bundle here "
                         "(DESIGN.md §16)")
    ap.add_argument("--flight-ring", type=int, default=64,
                    help="flight-recorder ring length (steps)")
    return ap.parse_args(argv)


def setup(args: argparse.Namespace):
    """(cfg, data pipeline, optimizer, its make_optimizer kwargs,
    TrainHyper) for a run — the launcher's whole configuration, so a caller
    can rebuild the same run, or a variant of it, from the library API
    (``make_optimizer(args.optimizer, **opt_kw)``,
    ``train_loop.jit_train_step(cfg, opt, hyper)``).  Compute is forced to
    f32."""
    cfg = cfgs.get_config(args.arch)
    over = {"param_dtype": "float32", "compute_dtype": "float32",
            "remat": "none"}
    if args.d_model:
        over.update(d_model=args.d_model, head_dim=args.d_model // cfg.n_heads)
    if args.n_layers:
        over["n_layers"] = args.n_layers
    if args.vocab:
        over["vocab_size"] = args.vocab
    if args.no_stable_embedding:
        over["stable_embedding"] = False
    cfg = dataclasses.replace(cfg, **over)

    pipe = SyntheticLMPipeline(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.batch, seed=1234))

    opt_kw = {}
    if args.optimizer.endswith("8"):
        opt_kw.update(qmap_m=args.qmap if args.qmap != "dynamic" else "dynamic",
                      qmap_r=args.qmap if args.qmap != "dynamic" else "dynamic",
                      blockwise_norm=not args.no_blockwise)
        if args.state_bits:
            parts = [int(b) for b in args.state_bits.split(",")]
            opt_kw["state_bits"] = parts[0] if len(parts) == 1 else tuple(parts)
        if args.no_32bit_embed_override:
            opt_kw["override_32bit"] = lambda p: False
    if args.shard_grads:
        opt_kw["shard_grads"] = True
    if args.overlap_buckets > 1:
        opt_kw["overlap_buckets"] = args.overlap_buckets
    if args.telemetry_every:
        opt_kw["telemetry_every"] = args.telemetry_every
    if args.sentinel:
        opt_kw["sentinel"] = True
    hyper = train_loop.TrainHyper(
        microbatches=args.microbatches,
        lr_schedule=train_loop.warmup_cosine(args.lr, args.warmup,
                                             args.steps))
    opt_kw = dict(lr=args.lr, weight_decay=0.0, **opt_kw)
    return cfg, pipe, make_optimizer(args.optimizer, **opt_kw), opt_kw, hyper


def main(argv=None):
    enable_compile_cache()
    args = parse_args(argv)
    cfg, pipe, opt, _, hyper = setup(args)

    # Telemetry (DESIGN.md §14): a typed registry over a JSONL sink, with
    # trace-time phase annotations enabled BEFORE the step is traced so the
    # compiled executable carries the phase scopes.  Without --telemetry-dir
    # nothing is enabled and the step lowers exactly as before.
    reg = probe = None
    if args.telemetry_dir:
        reg = tel.MetricRegistry()
        reg.add_sink(tel.JsonlSink(
            os.path.join(args.telemetry_dir, "telemetry.jsonl")))
        tracing.set_phase_tracing(True)
        tracing.reset_trace_events()
        probe = tel.QHealthProbe(opt)

    # §16 observability: host-side anomaly detectors over the step metrics
    # (always cheap) + the flight recorder's crash-forensics ring/snapshot.
    detector = tel.AnomalyDetector() if (args.sentinel or args.flight_dir) \
        else None
    flight = tel.FlightRecorder(ring=args.flight_ring) if args.flight_dir \
        else None
    telemetry_jsonl = (os.path.join(args.telemetry_dir, "telemetry.jsonl")
                       if args.telemetry_dir else None)

    def _flight_dump(reason, step):
        if flight is None:
            return
        path = flight.dump(args.flight_dir, reason=reason, trigger_step=step,
                           config=cfg, telemetry_path=telemetry_jsonl)
        print(f"[flight] dumped {reason} forensics to {path} "
              f"(last healthy snapshot: step {flight.snapshot_step})")

    # donated state (DESIGN.md §13c); the loop below rebinds state
    step_fn = train_loop.jit_train_step(cfg, opt, hyper)
    state, _ = train_loop.init_train_state(cfg, opt, jax.random.PRNGKey(args.seed))

    start = 0
    if args.ckpt_dir:
        latest = ckpt.latest_step(args.ckpt_dir)
        if latest is not None:
            state = ckpt.restore(args.ckpt_dir, latest,
                                 jax.eval_shape(lambda s: s, state))
            start = latest
            print(f"[resume] from step {latest}")

    stop = {"now": False}

    def _sig(_s, _f):   # preemption: checkpoint + clean exit
        stop["now"] = True
    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)

    out_f = open(args.out, "a") if args.out else None
    # single ms/step + compile_s definition (telemetry.tracing.StepTimer,
    # DESIGN.md §14) — the first executed step is the compile step and is
    # excluded from steady-state times and straggler z-scores
    timer = tracing.StepTimer()
    for i in range(start, args.steps):
        with timer.step():
            batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
        dt = timer.last_dt
        if i == start:
            print(f"[compile] first step {dt:.2f}s (excluded from ms/step)")
            if reg is not None:
                # per-phase dispatch accounting recorded while tracing the
                # step (one "trace" event per compile; DESIGN.md §14)
                reg.emit_event(tracing.trace_event_dict(i))
                tracing.reset_trace_events()
        if timer.is_straggler:
            print(f"[straggler] step {i}: {dt:.3f}s z={timer.straggler_z:.1f}")
        rec = {"step": i, "loss": loss, "t": round(dt, 4),
               "grad_norm": float(metrics["grad_norm"])}
        if i == start:
            rec["compile_s"] = round(timer.compile_s, 4)
        if out_f:
            out_f.write(json.dumps(rec) + "\n")
            out_f.flush()
        if reg is not None:
            reg.record_scalars(i, metrics, prefix="train/")
            reg.emit_event({"kind": "phase", "step": i, "phase": "step",
                            "wall_s": dt})
            if probe is not None and args.telemetry_every and \
                    (i + 1) % args.telemetry_every == 0:
                with tracing.host_phase("qhealth_probe", step=i):
                    qevs = list(probe.probe(state.opt_state, step=i))
                for ev in qevs:
                    reg.emit_event(ev)
                for ev in tracing.drain_phase_events():
                    reg.emit_event(ev)
                if detector is not None:
                    for ev in detector.observe_qhealth(qevs):
                        reg.emit_event(ev)
                        if flight is not None:
                            flight.note_anomaly(ev)
        # §16: escalate this step's metrics into anomaly events; a fatal
        # verdict aborts the run (after the flight dump).  The snapshot is
        # taken from the post-step state only when the step was healthy —
        # a poisoned state must never become the resume point.
        fatal_reason = None if np.isfinite(loss) else "nonfinite_loss"
        if detector is not None:
            for ev in detector.observe_step(i, metrics):
                if reg is not None:
                    reg.emit_event(ev)
                if flight is not None:
                    flight.note_anomaly(ev)
                print(f"[anomaly] step {i} [{ev['severity']}] "
                      f"{ev['reason']} value={ev['value']:.4g}")
                if ev["severity"] == "fatal" and fatal_reason is None:
                    fatal_reason = ev["reason"]
        if flight is not None:
            flight.record(i, metrics, wall_s=dt)
            if fatal_reason is None:
                flight.snapshot(i, state)
        if i % 20 == 0 or i == args.steps - 1:
            print(f"step {i:5d} loss {loss:.4f} ({dt:.2f}s)", flush=True)
        if args.ckpt_dir and ((i + 1) % args.ckpt_every == 0 or stop["now"]):
            ckpt.save(args.ckpt_dir, i + 1, state)
        if stop["now"]:
            print(f"[preempted] checkpointed at {i + 1}; exiting")
            return 0
        if fatal_reason is not None:
            print("[diverged]" if fatal_reason == "nonfinite_loss"
                  else f"[fatal anomaly] {fatal_reason}")
            if reg is not None:
                reg.flush(step=i)
                reg.close()
                tracing.set_phase_tracing(False)
            _flight_dump(fatal_reason, i)
            return 2
    sb = opt.state_bytes(state.opt_state) if hasattr(opt, "state_bytes") else {}
    steady_ms = timer.steady_ms()
    if reg is not None:
        reg.gauge("train/steady_ms").set(steady_ms)
        reg.gauge("train/compile_s").set(timer.compile_s)
        reg.flush(step=args.steps - 1)
        reg.close()
        tracing.set_phase_tracing(False)
    print(f"done. final loss {loss:.4f}; entropy floor "
          f"{pipe.bigram_entropy():.4f}; compile {timer.compile_s:.2f}s; "
          f"steady {steady_ms:.1f} ms/step; optimizer state bytes {sb}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
