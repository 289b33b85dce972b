"""Observability for the 8-bit stack (DESIGN.md §14).

Three pillars:
  * :mod:`repro.telemetry.qhealth` — scheduled quantization-health probes
    (saturation, codebook utilization, absmax drift, round-trip RMS);
  * :mod:`repro.telemetry.tracing` — step-phase annotations, trace-time
    dispatch accounting, host spans, and the shared ``StepTimer``
    (ms/step + compile_s single definition);
  * :mod:`repro.telemetry.registry` / :mod:`repro.telemetry.export` —
    typed metrics (counter/gauge/histogram) and the JSONL / in-memory /
    BENCH-trajectory sinks behind them;
  * :mod:`repro.telemetry.sentinel` / :mod:`repro.telemetry.flight` —
    the numerics sentinel's host-side anomaly detectors and the
    flight-recorder crash-forensics dump (DESIGN.md §16), inspected via
    ``python -m repro.telemetry.inspect``.

All of it is off by default and adds nothing to the jitted step when off
(pinned by tests/test_telemetry.py's zero-overhead guard and the
``train_step.sentinel_invariant`` compile contract).
"""
from repro.telemetry.export import (ANOMALY_SEVERITIES, BenchJsonSink,
                                    InMemorySink, JsonlSink,
                                    SCHEMA, append_json_trajectory,
                                    validate_event, validate_jsonl)
from repro.telemetry.flight import (FLIGHT_SCHEMA, FlightRecorder,
                                    config_hash, load_dump, restore_state)
from repro.telemetry.qhealth import QHealthProbe
from repro.telemetry.sentinel import (AnomalyDetector, HEALTH_SLOTS,
                                      anomaly_event)
from repro.telemetry.registry import MetricRegistry
from repro.telemetry.tracing import (StepTimer, annotate, drain_phase_events,
                                     host_phase, phase_tracing,
                                     phase_tracing_enabled,
                                     reset_trace_events, set_phase_tracing,
                                     span, trace_event_dict, trace_events)

__all__ = [
    "SCHEMA", "BenchJsonSink", "InMemorySink", "JsonlSink",
    "append_json_trajectory", "validate_event", "validate_jsonl",
    "ANOMALY_SEVERITIES", "AnomalyDetector", "HEALTH_SLOTS",
    "anomaly_event", "FLIGHT_SCHEMA", "FlightRecorder", "config_hash",
    "load_dump", "restore_state",
    "QHealthProbe", "MetricRegistry", "StepTimer", "annotate",
    "drain_phase_events", "host_phase", "phase_tracing",
    "phase_tracing_enabled", "reset_trace_events", "set_phase_tracing",
    "span", "trace_event_dict", "trace_events",
]
