"""Observability of the serving path (DESIGN.md §14, §17).

* zero overhead: with phase tracing off, the paged decode step, the
  prefill and the page commit lower to the same StableHLO as a build
  without the model's scopes, and carry no ``tel.`` scope;
* on: the compiled decode step names ``tel.attention``, ``tel.kv_append``,
  ``tel.kv_gather`` and ``tel.mlp``, changes no instruction and serves the
  same tokens;
* the scheduler's work counters equal a hand count of its bursts, with
  and without an eviction;
* ``serve()``'s host spans reach the profiler's trace.
"""
import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import contracts
from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.serve.kvcache import PagedKVConfig
from repro.serve.scheduler import (ContinuousBatchingEngine, Request,
                                   SchedulerConfig)
from repro.telemetry import MetricRegistry, tracing

CFG = ModelConfig(arch_id="t", family="dense", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=97,
                  head_dim=8, compute_dtype="float32", remat="none",
                  attn_chunk=16)
KV = PagedKVConfig(page_size=4, n_pages=16, n_slots=2, max_pages_per_seq=4,
                   kv_bits=8)
P = 5                                  # prompt length of the lowered prefill
COUNTERS = ("serve/sched/decode_steps", "serve/sched/bursts",
            "serve/sched/slot_steps", "serve/kv/pages_live",
            "serve/kv/pages_gathered")


@pytest.fixture(scope="module")
def params():
    return M.init_model(CFG, jax.random.PRNGKey(0))[0]


def _requests(shapes):
    rng = np.random.RandomState(3)
    return [Request(rid=i, prompt=tuple(rng.randint(0, 97, p).tolist()),
                    max_new_tokens=n) for i, (p, n) in enumerate(shapes)]


def _programs(params, kv=KV):
    """The engine's three serving programs (decode step, prefill and page
    commit for a P-token prompt), lowered; a new engine traces anew."""
    eng = ContinuousBatchingEngine(CFG, params, SchedulerConfig(kv=kv))
    i32 = jnp.int32
    vec = jnp.zeros((kv.n_slots,), i32)
    table = jnp.zeros((kv.n_slots, kv.max_pages_per_seq), i32)
    tokens = jnp.zeros((1, P), i32)
    _, dense = jax.eval_shape(eng._prefill_fn(P), params, tokens)
    return {
        "decode": eng._decode.lower(params, eng._last_tok, eng.caches, table,
                                    vec, vec, vec),
        "prefill": eng._prefill_fn(P).lower(params, tokens),
        "commit": eng._commit_fn(P).lower(eng.caches, dense, 0,
                                          table[0]),
    }


@contextlib.contextmanager
def _no_scopes():
    """The model as it was before its scopes: ``annotate`` does nothing."""
    saved = tracing.annotate
    tracing.annotate = contextlib.contextmanager(lambda phase: (yield))
    try:
        yield
    finally:
        tracing.annotate = saved


def _instructions(hlo: str) -> list:
    """The compiled module's instructions without their metadata."""
    return [re.sub(r",? ?metadata=\{[^}]*\}", "", line)
            for line in hlo.splitlines() if re.match(r"\s*(ROOT )?%", line)]


@pytest.fixture(scope="module")
def lowered(params):
    """{program: (flag off, no scopes at all, flag on)} lowerings."""
    with tracing.phase_tracing(False):
        off = _programs(params)
        with _no_scopes():
            bare = _programs(params)
    with tracing.phase_tracing(True):
        on = _programs(params)
    return {k: (off[k], bare[k], on[k]) for k in off}


@pytest.mark.parametrize("program", ["decode", "prefill", "commit"])
def test_serving_programs_lower_byte_identical_with_tracing_off(lowered,
                                                                program):
    off, bare, on = lowered[program]
    ok, detail = contracts.lowering_invariant(
        {"off": off.as_text(), "no_scopes": bare.as_text()})
    assert ok, detail
    assert "tel." not in off.compile().as_text()
    # the scopes ride location metadata only: the StableHLO is unchanged
    ok, detail = contracts.lowering_invariant(
        {"off": off.as_text(), "on": on.as_text()})
    assert ok, detail


def test_decode_step_scopes_on_and_tokens_unchanged(params, lowered):
    off, _, on = (x.compile().as_text() for x in lowered["decode"])
    scopes = set(re.findall(r"tel\.[a-z_]+", on))
    assert {"tel.attention", "tel.kv_append", "tel.kv_gather",
            "tel.mlp"} <= scopes
    assert "tel.attention/tel.kv_gather" in on          # nested in attention
    # metadata only: every instruction, name and operand stays as it was
    assert _instructions(on) == _instructions(off)

    reqs = _requests([(7, 6), (3, 9), (5, 4)])
    served = {}
    for flag in (False, True):
        with tracing.phase_tracing(flag):
            eng = ContinuousBatchingEngine(CFG, params,
                                           SchedulerConfig(kv=KV))
            served[flag] = eng.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(served[False][r.rid],
                                      served[True][r.rid])


def _totals(bursts, kv):
    """Counter values from a hand-listed schedule [(live slots, their
    allocated pages, k)]."""
    return {
        "serve/sched/decode_steps": sum(k for _, _, k in bursts),
        "serve/sched/bursts": len(bursts),
        "serve/sched/slot_steps": sum(s * k for s, _, k in bursts),
        "serve/kv/pages_live": sum(p * k for _, p, k in bursts),
        "serve/kv/pages_gathered": sum(kv.n_slots * kv.max_pages_per_seq * k
                                       for _, _, k in bursts),
    }


# Page size 4, two slots.  A request of P prompt tokens admits with the
# pages of P + 1 positions and a burst runs until the first completion or
# page boundary of a live slot.
HAND_COUNTS = {
    # rid0 (3 + 6) and rid1 (5 + 3): [1 page at 3, 2 pages at 5] -> 1
    # step (rid0's boundary at 4); rid0 extends -> 1 step (rid1 done at
    # 3 tokens); rid0 alone from 5 to 8 -> 3 steps
    "no_eviction": (16, [(3, 6), (5, 3)], [(2, 3, 1), (2, 4, 1),
                                           (1, 2, 3)], 0),
    # a 3-page pool.  rid0 (3 + 6) and rid1 (3 + 5) take a page each ->
    # 1 step; both cross into a 2nd page: rid0 takes the last one, rid1
    # finds the pool dry and preempts rid0 (the younger one is rid1
    # itself); rid1 alone from 4 to 7 -> 3 steps, done; rid0 readmits
    # from its start -> 1 step, extends -> 4 steps
    "eviction": (3, [(3, 6), (3, 5)], [(2, 2, 1), (1, 2, 3), (1, 1, 1),
                                       (1, 2, 4)], 1),
}


@pytest.mark.parametrize("case", sorted(HAND_COUNTS))
def test_scheduler_counters_match_hand_count(params, case):
    n_pages, shapes, bursts, evictions = HAND_COUNTS[case]
    kv = PagedKVConfig(page_size=4, n_pages=n_pages, n_slots=2,
                       max_pages_per_seq=4, kv_bits=8)
    reg = MetricRegistry()
    eng = ContinuousBatchingEngine(CFG, params, SchedulerConfig(kv=kv),
                                   registry=reg)
    out = eng.serve(_requests(shapes))
    got = reg.metrics()
    assert {name: got[name] for name in COUNTERS} == _totals(bursts, kv)
    assert got.get("serve/sched/evictions", 0) == evictions
    assert [len(out[i]) for i in range(len(shapes))] == \
        [n for _, n in shapes]


def test_span_is_nullcontext_when_off():
    with tracing.phase_tracing(False):
        assert isinstance(tracing.span("serve"), contextlib.nullcontext)
    with tracing.phase_tracing(True):
        sp = tracing.span("serve", rid=1)
        assert not isinstance(sp, contextlib.nullcontext)
        with sp:
            pass


def test_serve_writes_host_spans_to_the_profiler(params, tmp_path):
    from jax.profiler import ProfileData
    reqs = _requests([(3, 6), (5, 3)])
    eng = ContinuousBatchingEngine(CFG, params, SchedulerConfig(kv=KV))
    eng.serve(reqs)                        # compile outside the trace
    with tracing.phase_tracing(True):
        jax.profiler.start_trace(str(tmp_path))
        try:
            eng.serve(reqs)
        finally:
            jax.profiler.stop_trace()
    files = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    assert len(files) == 1
    host = [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(files[0]).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("tel.")]
    names = [n for n, _ in host]
    assert names.count("tel.serve") == 1
    assert {"tel.serve.admit", "tel.serve.prefill", "tel.serve.commit",
            "tel.serve.extend", "tel.serve.upload", "tel.serve.burst",
            "tel.serve.complete", "tel.serve.sync",
            "tel.serve.collect"} <= set(names)
    # spans of one request carry its rid; a burst carries its k
    for name in ("tel.serve.admit", "tel.serve.complete"):
        assert sorted(st["rid"] for n, st in host if n == name) == [0, 1]
    assert sum(st["k"] for n, st in host if n == "tel.serve.burst") == 5
