"""The model-layer readers (``attention_ms``, ``mlp_ms``) against the
scopes the train step really carries, every reader's value on the recorded
trace, and the serving engine's work counters on the ``shortctx_out96``
wave's shapes."""
import gzip
import json
import pathlib

import jax
import pytest

from chipbench import peaks, registry, trace
from chipbench.entries import serve as S
from chipbench.entries import train as T
from chipbench.trace import Event

DATA = pathlib.Path(__file__).resolve().parent / "data"
SEED = 2 ** 31 + 11

# Every reader's value on the recorded trace, as read before the program
# had its model scopes: those scopes move none of them.  The trace
# predates them, so the layer readers read nothing there.
RECORDED_READINGS = {
    "train_mfu": 24.06112382158895,
    "fwd_bwd_ms": 644.9854575,
    "opt_update_ms": 433.1641965,
    "fused_update_roofline": 1.2376068841685006,
    "device_idle.train": 0.0023459070212283883,
    "device_idle.serve": 0.0023459070212283883,
    "decode_step_ms": None, "prefill_ms": None, "kv_gather_ms": None,
    "serve_mfu": None, "attention_ms": None, "mlp_ms": None,
}


@pytest.fixture(scope="module")
def recorded():
    doc = json.loads(gzip.open(DATA / "train_adam8_2steps.json.gz", "rt")
                     .read())
    return [Event(*e) for e in doc["events"]], doc["scopes"]


def test_every_reader_has_a_recorded_reading():
    assert set(RECORDED_READINGS) == {
        p.stem for p in (registry.BENCH_DIR / "metrics").glob("*.py")}


@pytest.mark.parametrize("name", sorted(RECORDED_READINGS))
def test_readers_on_the_recorded_trace(recorded, name):
    events, scopes = recorded
    lo, hi = trace.window(events, "cb.window")
    traffic = json.loads((registry.BENCH_DIR / "traffic" /
                          "adam8_64x512.json").read_text())
    record = {"steps": 2, "peaks": peaks.lookup("TPU v5 lite"), "chips": 1,
              "tokens_per_step": traffic["global_batch"]
              * traffic["seq_len"],
              "config": json.loads((registry.BENCH_DIR / "configs" /
                                    "paper-lm-209m.json").read_text()),
              "traffic": traffic, "events": events, "scopes": scopes,
              "window": (lo, hi)}
    got = registry.metric_reader(name).read(record)
    want = RECORDED_READINGS[name]
    assert got == (None if want is None else pytest.approx(want, rel=1e-12))


@pytest.fixture(scope="module")
def train_scopes(tmp_path_factory):
    """Scope paths of the tiny train step as the train entry compiles it
    (phase tracing on)."""
    from chipbench.tests import tiny
    from repro.kernels import ops
    from repro.telemetry import tracing
    bench = tiny.make(tmp_path_factory.mktemp("layers"))
    cell = registry.resolve("tiny-train8", bench.parent, bench)
    mp = pytest.MonkeyPatch()
    mp.setattr(ops, "default_impl", lambda: "jnp")
    was = tracing.phase_tracing_enabled()
    try:
        step = T.build(cell, SEED)[1]
    finally:
        tracing.set_phase_tracing(was)
        mp.undo()
    return trace.scope_map(step.as_text())


def _one_ns_each(scopes):
    """One run of the step in which every instruction of ``scopes`` takes
    1 ns, one after another."""
    names = sorted(scopes)
    events = [Event(-1, "host", "cb.window", 0.0, float(len(names) + 2)),
              Event(0, "modules", "jit_train_step", 1.0, float(len(names)))]
    events += [Event(0, "ops", n, 1.0 + i, 1.0) for i, n in enumerate(names)]
    return {"events": events, "scopes": scopes,
            "window": (0.0, float(len(names) + 2))}


def _leaf_ns(scopes, select) -> int:
    return sum(1 for n, p in scopes.items()
               if not trace.CONTROL_FLOW.match(n) and select(p))


@pytest.mark.parametrize("metric,scope", [("attention_ms", "tel.attention"),
                                          ("mlp_ms", "tel.mlp")])
def test_layer_readers_count_forward_and_backward(train_scopes, metric,
                                                  scope):
    """Each layer reader counts every op whose scope path holds its
    layer's scope, the backward ones (``transpose``) included, and reads
    nothing from a step compiled without the layer scopes."""
    read = registry.metric_reader(metric).read
    rec = _one_ns_each(train_scopes)
    ns = _leaf_ns(train_scopes, lambda p: scope in p)
    back = _leaf_ns(train_scopes, lambda p: scope in p and "transpose" in p)
    assert back > 0 and ns > back
    assert read(rec) == pytest.approx(ns * 1e-6)
    fwd_bwd = registry.metric_reader("fwd_bwd_ms").read(rec)
    inside = _leaf_ns(train_scopes, lambda p: scope in p
                      and "tel.forward_backward" in p)
    assert fwd_bwd >= inside * 1e-6 > 0
    bare = {n: p.replace(scope, "") for n, p in train_scopes.items()}
    assert read(_one_ns_each(bare)) is None


def test_shortctx_wave_counts(monkeypatch):
    """One wave of the ``shortctx_out96`` mix through the engine at toy
    widths: the scheduler's bursts depend only on the shapes, so these
    are the counts the chip's run makes (72.0 % of the slot-steps live,
    25.5 % of the gathered pages)."""
    from repro.configs.base import ModelConfig
    from repro.kernels import ops
    from repro.models import model as M
    from repro.serve.kvcache import PagedKVConfig
    from repro.serve.scheduler import ContinuousBatchingEngine, \
        SchedulerConfig
    from repro.telemetry import MetricRegistry
    monkeypatch.setattr(ops, "default_impl", lambda: "jnp")
    traffic = json.loads((registry.BENCH_DIR / "traffic" /
                          "shortctx_out96.json").read_text())
    cfg = ModelConfig(arch_id="t", family="dense", n_layers=1, d_model=16,
                      n_heads=2, n_kv_heads=2, d_ff=16, vocab_size=64,
                      head_dim=8, compute_dtype="float32", remat="none",
                      attn_chunk=64)
    params = M.init_model(cfg, jax.random.PRNGKey(0))[0]
    reg = MetricRegistry()
    engine = ContinuousBatchingEngine(cfg, params, SchedulerConfig(
        kv=PagedKVConfig(**traffic["kv"]), temperature=0.0), registry=reg)
    out = engine.serve(S.wave_requests(traffic, 64, SEED, 0))
    counts = {n: reg.counter(n).value for n in (
        "serve/sched/decode_steps", "serve/sched/bursts",
        "serve/sched/slot_steps", "serve/kv/pages_live",
        "serve/kv/pages_gathered")}
    assert sum(len(t) for t in out.values()) == 1479
    assert counts == {"serve/sched/decode_steps": 127,
                      "serve/sched/bursts": 19,
                      "serve/sched/slot_steps": 1463,
                      "serve/kv/pages_live": 16558,
                      "serve/kv/pages_gathered": 65024}
    n_slots = traffic["kv"]["n_slots"]
    assert round(100 * 1463 / (127 * n_slots), 1) == 72.0
    assert round(100 * 16558 / 65024, 1) == 25.5
