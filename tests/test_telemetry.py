"""Telemetry (DESIGN.md §14): typed registry, JSONL schema, qhealth
probes vs an oracle, step-phase tracing, and the zero-overhead guard.

The central contract: with telemetry off, the jitted train step lowers to
byte-identical StableHLO (so the goldens and every perf number are
untouched); with it on, the probes run as a separate jitted executable on
the host schedule and the recorded health matches an independent
numpy/jnp oracle exactly — including packed sub-byte codes and the
ZeRO-1 partitioned arena on a 4-device mesh."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import mesh_of, tiny_cfg, tiny_pipe
from repro import telemetry as tel
from repro.core.lowbit import unpack_codes, unwrap_codes
from repro.core.optim import make_optimizer
from repro.core.optim.base import Quant8Leaf
from repro.telemetry import tracing
from repro.telemetry.export import append_json_trajectory, validate_event
from repro.train import loop as L


# ------------------------------------------------------------- registry
def test_registry_typed_metrics_round_trip():
    reg = tel.MetricRegistry()
    sink = tel.InMemorySink()
    reg.add_sink(sink)
    assert reg.counter("serve/requests").inc(3) == 3
    assert reg.counter("serve/requests").inc() == 4      # get-or-create
    reg.gauge("train/loss").set(jnp.float32(2.5))        # jax scalar ok
    reg.histogram("q/util", n_bins=4).observe_counts([1, 0, 2, 7])
    reg.flush(step=5)
    assert reg.metrics() == {"serve/requests": 4, "train/loss": 2.5,
                             "q/util": [1, 0, 2, 7]}
    assert reg.get("train/loss") == 2.5
    assert reg.get("never/registered") is None
    evs = sink.events
    assert len(evs) == 3
    by_name = {e["name"]: e for e in evs}
    assert by_name["serve/requests"]["type"] == "counter"
    assert by_name["serve/requests"]["value"] == 4
    assert by_name["q/util"]["value"] == [1, 0, 2, 7]
    for e in evs:
        assert validate_event(e) == [], e
        assert e["step"] == 5


def test_registry_type_mismatch_raises():
    reg = tel.MetricRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    reg.histogram("h", n_bins=16)
    with pytest.raises(TypeError):
        reg.histogram("h", n_bins=256)     # bin-count mismatch
    with pytest.raises(TypeError):
        reg.counter("h")


def test_record_scalars_routes_gauges_and_skips_arrays():
    reg = tel.MetricRegistry()
    sink = tel.InMemorySink()
    reg.add_sink(sink)
    reg.record_scalars(3, {"loss": jnp.float32(1.5),
                           "grad_norm": np.float64(0.25),
                           "not_scalar": jnp.zeros((4,))}, prefix="train/")
    assert reg.get("train/loss") == 1.5
    assert reg.get("train/grad_norm") == 0.25
    assert reg.get("train/not_scalar") is None
    assert {e["name"] for e in sink.events} == {"train/loss",
                                                "train/grad_norm"}
    assert all(e["step"] == 3 and validate_event(e) == []
               for e in sink.events)


# ----------------------------------------------------------- JSONL schema
def test_jsonl_sink_and_schema_validation(tmp_path):
    path = str(tmp_path / "t.jsonl")
    reg = tel.MetricRegistry()
    reg.add_sink(tel.JsonlSink(path))
    reg.gauge("a").set(1.0)
    reg.flush(step=0)
    reg.emit_event({"kind": "phase", "step": 1, "phase": "step",
                    "wall_s": 0.01})
    reg.emit_event({"kind": "trace", "step": 1, "phases": []})
    reg.close()
    events, errors = tel.validate_jsonl(path)
    assert errors == []
    assert [e["kind"] for e in events] == ["metric", "phase", "trace"]
    assert all(e["schema"] == tel.SCHEMA for e in events)


def test_validate_event_rejects_malformed():
    assert validate_event("not a dict")
    assert validate_event({"kind": "nope"})
    # missing required fields + missing schema stamp
    errs = validate_event({"kind": "qhealth", "step": 1})
    assert any("missing field" in e for e in errs)
    assert any("schema" in e for e in errs)
    # bad metric type / non-int step
    assert validate_event({"kind": "metric", "schema": tel.SCHEMA,
                           "step": "x", "name": "a", "type": "timer",
                           "value": 1})
    # histogram value must be a list
    assert validate_event({"kind": "metric", "schema": tel.SCHEMA,
                           "step": 1, "name": "a", "type": "histogram",
                           "value": 3})


def test_validate_jsonl_flags_bad_lines(tmp_path):
    path = str(tmp_path / "bad.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "phase", "schema": tel.SCHEMA,
                            "step": 0, "phase": "x", "wall_s": 0.1}) + "\n")
        f.write("not json\n")
        f.write(json.dumps({"kind": "metric", "schema": tel.SCHEMA,
                            "step": 0}) + "\n")
    events, errors = tel.validate_jsonl(path)
    assert len(events) == 2
    assert any("not JSON" in e for e in errors)
    assert any("missing field" in e for e in errors)


def test_append_json_trajectory_dedupes(tmp_path):
    path = str(tmp_path / "B.json")
    append_json_trajectory(path, {"bench": "a", "git_sha": "s1", "v": 1},
                           dedupe_fields=("bench", "git_sha"))
    append_json_trajectory(path, {"bench": "a", "git_sha": "s1", "v": 2},
                           dedupe_fields=("bench", "git_sha"))
    append_json_trajectory(path, {"bench": "a", "git_sha": "s2", "v": 3},
                           dedupe_fields=("bench", "git_sha"))
    with open(path) as f:
        entries = json.load(f)["entries"]
    assert [(e["git_sha"], e["v"]) for e in entries] == [("s1", 2),
                                                         ("s2", 3)]
    # corrupt file tolerated: starts a fresh trajectory
    with open(path, "w") as f:
        f.write("{broken")
    append_json_trajectory(path, {"bench": "a", "git_sha": "s1", "v": 9},
                           dedupe_fields=("bench", "git_sha"),
                           defaults={"tag": "d"})
    with open(path) as f:
        entries = json.load(f)["entries"]
    assert entries == [{"bench": "a", "git_sha": "s1", "v": 9, "tag": "d"}]


def test_append_json_trajectory_stamps_unknown_git_sha(tmp_path):
    """Entries written without a resolvable git_sha (detached/missing
    checkout) are stamped "unknown" — git_sha is a dedupe key and must
    always be present (§16 satellite)."""
    path = str(tmp_path / "B.json")
    append_json_trajectory(path, {"bench": "a", "v": 1},
                           dedupe_fields=("bench",))
    with open(path) as f:
        entries = json.load(f)["entries"]
    assert entries[0]["git_sha"] == "unknown"
    # an explicit sha is never clobbered
    append_json_trajectory(path, {"bench": "b", "git_sha": "cafe", "v": 2},
                           dedupe_fields=("bench",))
    with open(path) as f:
        entries = json.load(f)["entries"]
    assert entries[1]["git_sha"] == "cafe"


def test_bench_json_sink_routes_events(tmp_path):
    path = str(tmp_path / "B.json")
    reg = tel.MetricRegistry()
    reg.add_sink(tel.BenchJsonSink(path, dedupe_fields=("name",),
                                   defaults={"git_sha": "deadbeef"}))
    reg.gauge("x").set(1.0)
    reg.flush(step=0)
    reg.gauge("x").set(2.0)
    reg.flush(step=1)
    with open(path) as f:
        entries = json.load(f)["entries"]
    assert len(entries) == 1                 # deduped on name
    assert entries[0]["value"] == 2.0
    assert entries[0]["git_sha"] == "deadbeef"


# --------------------------------------------------- qhealth vs an oracle
def _oracle_events(opt, state):
    """Independent numpy recomputation of every arena qhealth field."""
    arena = state.arena
    out = {}
    for slot, codes, absmax, qmap in (
            ("m", arena.codes_m, arena.absmax_m, opt._qmap1),
            ("r", arena.codes_r, arena.absmax_r, opt._qmap2)):
        if codes is None:
            continue
        raw, rbits, _ = unwrap_codes(codes)
        bits = rbits if rbits is not None else 8
        c = np.asarray(unpack_codes(raw, bits)).astype(np.int64)
        q = np.abs(np.asarray(qmap))
        n_bins = q.shape[-1]
        is_edge = q[c] >= q.max()
        am = np.asarray(absmax)
        bsz = c.shape[1]
        for s in arena.segments:
            nvb = max(min(-(-s.n // bsz), s.n_blocks), 1)
            cs = c[s.offset:s.offset + nvb]
            es = is_edge[s.offset:s.offset + nvb]
            valid = (np.arange(nvb * bsz).reshape(nvb, bsz) < s.n)
            out[(s.path, slot)] = {
                "bits": bits, "n_bins": n_bins,
                "saturation_fraction": float(
                    np.sum(np.any(es & valid, axis=1)) / nvb),
                "edge_code_fraction": float(np.sum(es & valid)
                                            / np.sum(valid)),
                "util_hist": np.bincount(cs.reshape(-1)[valid.reshape(-1)],
                                         minlength=n_bins)[:n_bins],
                "absmax_mean": float(np.mean(am[s.offset:s.offset + nvb])),
            }
    return out


def _probe_map(events):
    return {(e["segment"], e["slot"]): e for e in events
            if e["target"] == "arena"}


def _check_probe_vs_oracle(opt, state, step=1):
    probe = tel.QHealthProbe(opt)
    got = _probe_map(probe.probe(state, step=step))
    want = _oracle_events(opt, state)
    assert set(got) == set(want)
    assert len(want) > 0
    for key, w in want.items():
        g = got[key]
        assert g["bits"] == w["bits"], key
        assert g["n_bins"] == w["n_bins"], key
        np.testing.assert_array_equal(np.asarray(g["util_hist"]),
                                      w["util_hist"], err_msg=str(key))
        np.testing.assert_allclose(g["saturation_fraction"],
                                   w["saturation_fraction"], rtol=1e-6)
        np.testing.assert_allclose(g["edge_code_fraction"],
                                   w["edge_code_fraction"], rtol=1e-6)
        np.testing.assert_allclose(g["absmax_mean"], w["absmax_mean"],
                                   rtol=1e-5)
        assert g["absmax_drift"] == 1.0      # first probe: EMA baseline
        assert g["util_fraction"] == pytest.approx(
            float(np.mean(w["util_hist"] > 0)))
    return got


def _arena_opt(**kw):
    return make_optimizer("adam8", lr=1e-2, min_8bit_size=256,
                          override_32bit=lambda p: False, **kw)


def _params():
    key = jax.random.PRNGKey(7)
    return {"a": jax.random.normal(key, (3000,)),          # padded tail
            "b": jax.random.normal(jax.random.fold_in(key, 1), (64, 48))}


def test_qhealth_probe_matches_oracle_8bit():
    opt = _arena_opt()
    state = opt.init(_params())
    _, state = opt.apply(jax.tree_util.tree_map(lambda p: p * 0.01,
                                                _params()), state)
    got = _check_probe_vs_oracle(opt, state)
    # padding is masked: histogram counts == live elements, not capacity
    for (path, slot), e in got.items():
        n = {"a": 3000, "b": 64 * 48}[path]
        assert sum(e["util_hist"]) == n, (path, slot)
        # masters-backed m slot carries the sampled round-trip error
        if slot == "m":
            assert 0.0 < e["rms_error"] < 0.2, e["rms_error"]
            assert e["rms_sample_blocks"] >= 1


def test_qhealth_probe_matches_oracle_packed_4bit():
    opt = _arena_opt(state_bits=(4, 8))
    state = opt.init(_params())
    _, state = opt.apply(jax.tree_util.tree_map(lambda p: p * 0.01,
                                                _params()), state)
    got = _check_probe_vs_oracle(opt, state)
    bins = {e["slot"]: e["n_bins"] for e in got.values()}
    assert bins == {"m": 16, "r": 256}       # 2^bits bins per slot


def test_qhealth_probe_partitioned_matches_unpartitioned():
    """ZeRO-1 partitioned state probes to the same health numbers as the
    unpartitioned oracle run (the probe replicates the arena through the
    §12 reduction-order mechanism; shard_multiple padding is excluded by
    the live-block masks)."""
    mesh = mesh_of(4)
    params = _params()
    grads = jax.tree_util.tree_map(lambda p: p * 0.01, params)

    opt_u = _arena_opt()
    st_u = opt_u.init(params)
    _, st_u = opt_u.apply(grads, st_u)
    base = _probe_map(tel.QHealthProbe(opt_u).probe(st_u, step=1))

    opt_p = _arena_opt(mesh=mesh, partition=True, partition_shards=4)
    st_p = opt_p.init(params)
    _, st_p = opt_p.apply(grads, st_p)
    part = _probe_map(tel.QHealthProbe(opt_p, mesh=mesh).probe(st_p,
                                                               step=1))

    assert set(base) == set(part)
    for key in base:
        for f in ("saturation_fraction", "edge_code_fraction",
                  "absmax_mean", "util_fraction"):
            np.testing.assert_allclose(part[key][f], base[key][f],
                                       rtol=1e-6, err_msg=f"{key} {f}")
        np.testing.assert_array_equal(part[key]["util_hist"],
                                      base[key]["util_hist"],
                                      err_msg=str(key))


def test_qhealth_probe_muon_leaf_events():
    """Muon matrix leaves live per-leaf (Quant8Leaf): the probe must emit
    target="leaf" events for them with the m-slot round-trip error, plus
    arena events for the pooled element-wise leaves."""
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (32, 64)),
              "v": jax.random.normal(jax.random.fold_in(key, 1), (1024,))}
    opt = make_optimizer("muon8", lr=1e-2, min_8bit_size=256,
                         override_32bit=lambda p: False)
    state = opt.init(params)
    _, state = opt.apply(jax.tree_util.tree_map(lambda p: p * 0.01, params),
                         state)
    assert any(isinstance(l, Quant8Leaf)
               for l in jax.tree_util.tree_leaves(
                   state.leaves,
                   is_leaf=lambda x: isinstance(x, Quant8Leaf)))
    events = tel.QHealthProbe(opt).probe(state, step=0)
    leaf = [e for e in events if e["target"] == "leaf"]
    assert {e["segment"] for e in leaf} == {"w"}
    assert {e["slot"] for e in leaf} == {"m"}    # single-moment muon
    assert all(len(e["util_hist"]) == 256 for e in leaf)
    assert all(sum(e["util_hist"]) == 32 * 64 for e in leaf)
    assert all("rms_error" in e for e in leaf)
    arena = [e for e in events if e["target"] == "arena"]
    assert {e["segment"] for e in arena} == {"v"}
    for e in events:
        assert validate_event({**e, "schema": tel.SCHEMA}) == [], e


def test_qhealth_drift_ema():
    probe = tel.QHealthProbe(_arena_opt(), ema_decay=0.5)
    key = ("arena", "x", "m")
    assert probe._drift(key, 2.0) == 1.0          # first probe: baseline
    assert probe._drift(key, 4.0) == pytest.approx(2.0)   # 4.0 / ema(2.0)
    # ema after the 2nd read: 0.5*2 + 0.5*4 = 3
    assert probe._drift(key, 3.0) == pytest.approx(1.0)


# ------------------------------------------------- zero-overhead guard
def test_telemetry_off_step_lowers_byte_identical():
    """telemetry_every is host-schedule only: configs 0 vs 2 lower the
    jitted train step to the SAME StableHLO, with the same donation
    aliasing — the §14 zero-overhead contract (pattern: the §13c
    donation_aliases audit)."""
    from repro.analysis import contracts

    cfg = tiny_cfg()
    pipe = tiny_pipe(vocab_size=cfg.vocab_size)
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    texts, aliases = {}, []
    for every in (0, 2):
        opt = make_optimizer("adam8", lr=5e-3, min_8bit_size=1024,
                             telemetry_every=every)
        state, _ = L.init_train_state(cfg, opt, jax.random.PRNGKey(0))
        lowered = L.jit_train_step(cfg, opt).lower(state, batch)
        texts[every] = lowered.as_text()
        aliases.append(L.donation_aliases(lowered))
    # the §14 guard is now the lowering_invariant contract (DESIGN.md §15)
    ok, detail = contracts.lowering_invariant(texts)
    assert ok, detail
    assert "tel." not in texts[0]        # annotations are literal no-ops
    assert aliases[0] == aliases[1] > 0


def test_phase_tracing_scopes_and_bit_identical_loss():
    """With tracing enabled at trace time the compiled step carries the
    tel.* scopes and the trace events record the fused dispatches — and
    the computed values are bit-identical to the untraced step."""
    cfg = tiny_cfg()
    pipe = tiny_pipe(vocab_size=cfg.vocab_size)

    def run(trace):
        opt = make_optimizer("adam8", lr=5e-3, min_8bit_size=1024)
        state, _ = L.init_train_state(cfg, opt, jax.random.PRNGKey(0))
        with tracing.phase_tracing(trace):
            tracing.reset_trace_events()
            step = L.jit_train_step(cfg, opt)
            losses = []
            for i in range(2):
                batch = {k: jnp.asarray(v)
                         for k, v in pipe.batch_at(i).items()}
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
            evs = tracing.trace_events()
            compiled = L.jit_train_step(cfg, opt, donate=False).lower(
                state, batch).compile()
        return losses, evs, compiled.as_text()

    losses_off, evs_off, text_off = run(False)
    losses_on, evs_on, text_on = run(True)
    assert losses_on == losses_off            # scopes never change values
    assert evs_off == []
    # named scopes ride op metadata: visible in the compiled HLO only
    assert "tel." not in text_off
    assert "tel." in text_on
    phases = {e["phase"] for e in evs_on}
    assert "forward_backward" in phases
    assert "optimizer_update" in phases
    assert any(p.startswith("fused_update.") for p in phases)
    # dispatch accounting rides the trace events (DESIGN.md §10)
    assert sum(e["dispatches"] for e in evs_on
               if e["phase"] == "optimizer_update") >= 1
    ev = tracing.trace_event_dict(0)
    assert ev["kind"] == "trace" and isinstance(ev["phases"], list)


def test_annotate_noop_when_disabled():
    tracing.reset_trace_events()
    with tracing.annotate("x"):
        pass
    assert tracing.trace_events() == []
    with tracing.phase_tracing(True):
        tracing.reset_trace_events()
        with tracing.annotate("x"):
            pass
        evs = tracing.trace_events()
    assert [e["phase"] for e in evs] == ["x"]
    assert evs[0]["dispatches"] == 0
    tracing.reset_trace_events()


def test_host_phase_timeline():
    with tracing.host_phase("probe", step=3):
        pass
    evs = tracing.drain_phase_events()
    assert len(evs) == 1
    assert evs[0]["kind"] == "phase" and evs[0]["phase"] == "probe"
    assert evs[0]["step"] == 3 and evs[0]["wall_s"] >= 0.0
    assert tracing.drain_phase_events() == []     # drained


def test_annotate_is_device_only_and_host_phase_opens_a_span(tmp_path):
    """Under the profiler, tracing an annotated function writes no host
    span (the scope belongs to the compiled ops, not to trace time),
    while ``host_phase`` opens its run-time ``tel.`` span and keeps its
    JSONL event."""
    import glob
    from jax.profiler import ProfileData

    def f(x):
        with tracing.annotate("scoped"):
            return x * 2.0

    with tracing.phase_tracing(True):
        jax.profiler.start_trace(str(tmp_path))
        try:
            jax.jit(f).lower(jnp.ones((4,))).compile()
            with tracing.host_phase("probe", step=1):
                pass
        finally:
            jax.profiler.stop_trace()
        tracing.reset_trace_events()
    [path] = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    names = [e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("tel.")]
    assert names == ["tel.probe"]
    [ev] = tracing.drain_phase_events()
    assert ev["phase"] == "probe" and ev["step"] == 1


# ------------------------------------------------------------ StepTimer
def test_step_timer_compile_split_and_straggler():
    t = tracing.StepTimer(window=5, z_threshold=3.0)
    t.record(10.0)                    # compile step
    assert t.compile_s == 10.0
    assert np.isnan(t.steady_ms())    # no steady samples yet
    # jittered steady steps, like a real clock (the exactly-constant
    # window is pinned separately by the zero-variance regression test)
    steady = [0.1, 0.11, 0.09, 0.1, 0.105, 0.095, 0.1, 0.11]
    for dt in steady:
        t.record(dt)
    assert t.steady_ms() == pytest.approx(1e3 * np.mean(steady))
    assert not t.is_straggler
    t.record(5.0)                     # ~50x the window: straggler
    assert t.is_straggler and t.straggler_z > 3.0
    assert t.compile_s == 10.0        # unchanged by steady steps
    s = t.summary()
    assert s["compile_s"] == 10.0 and s["n_steps"] == 10


def test_step_timer_zero_variance_window_scores_zero():
    """A zero-variance trailing window has no scale to judge deviation
    against: the z-score must be 0.0 ("no evidence"), not the inf/NaN an
    epsilon divide produced (§16 satellite regression)."""
    t = tracing.StepTimer(window=5, z_threshold=3.0)
    t.record(1.0)                     # compile step
    for _ in range(8):
        t.record(0.1)                 # bit-identical steps: std == 0
    t.record(50.0)                    # 500x jump, but no variance baseline
    assert t.straggler_z == 0.0
    assert np.isfinite(t.straggler_z)
    assert not t.is_straggler


def test_step_timer_context_manager():
    t = tracing.StepTimer()
    with t.step():
        pass
    with t.step():
        pass
    assert t.compile_s is not None and len(t.times) == 1


# -------------------------------------------------------- serve counters
def test_serve_engine_counters():
    from repro.configs.base import ModelConfig
    from repro.models import model as M
    from repro.serve.engine import ServeConfig, ServeEngine
    cfg = ModelConfig(arch_id="t", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=97,
                      head_dim=8, compute_dtype="float32", remat="none",
                      attn_chunk=16)
    params, _ = M.init_model(cfg, jax.random.PRNGKey(0))
    reg = tel.MetricRegistry()
    eng = ServeEngine(cfg, params, ServeConfig(max_len=64), registry=reg)
    prompts = np.ones((3, 4), np.int32)
    eng.generate(prompts, max_new_tokens=5)
    eng.generate(prompts, max_new_tokens=0)   # counted as a request too
    assert reg.get("serve/requests") == 6
    assert reg.get("serve/prompt_tokens") == 2 * 3 * 4
    assert reg.get("serve/generated_tokens") == 3 * 5
    # no registry -> no counters, no crash
    eng2 = ServeEngine(cfg, params, ServeConfig(max_len=64))
    eng2.generate(prompts, max_new_tokens=1)
