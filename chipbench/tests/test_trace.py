"""The trace reduction, on hand-made events and on a recorded trace of
the train-lm209m-adam8 cell (TPU v5 lite)."""
import gzip
import json
import pathlib

import pytest

from chipbench import trace
from chipbench.trace import Event

DATA = pathlib.Path(__file__).resolve().parent / "data"


def ev(name, start, dur, line="ops", device=0):
    return Event(device, line, name, float(start), float(dur))


def synthetic():
    """One device, window [0, 100): a while op (10-60) holding two body
    ops, a kernel, an all-reduce half hidden behind a fusion, gaps."""
    return [
        ev("cb.window", 0, 100, "host", -1),
        ev("cb.batch_upload", 60, 12, "host", -1),
        ev("jit_train_step", 5, 90, "modules"),
        ev("copy.1", 5, 5),
        ev("while.3", 10, 50),
        ev("fusion.7", 12, 20),
        ev("fusion.8", 35, 20),
        ev("fused_update_pallas.1", 72, 10),
        ev("all-reduce.2", 85, 10),
        ev("fusion.9", 85, 4),
    ]


def test_busy_leaf_ops_and_scopes():
    e = synthetic()
    lo, hi = trace.window(e, "cb.window")
    assert (lo, hi) == (0.0, 100.0)
    # union: 5-60, 72-82, 85-95 -> 55 + 10 + 10 ns
    assert trace.busy_s(e, lo, hi) == pytest.approx(75e-9)
    leaves = {x.name for x in trace.leaf_ops(e, 0)}
    assert "while.3" not in leaves and {"fusion.7", "fusion.8"} <= leaves
    assert trace.op_time_s(e, lo, hi, lambda n: n.startswith(
        "fused_update_pallas")) == pytest.approx(10e-9)
    scopes = {"fusion.7": "jit(step)/tel.forward_backward/dot",
              "fusion.8": "jit(step)/tel.optimizer_update/mul"}
    fb = trace.op_time_s(e, lo, hi, lambda n: "tel.forward_backward"
                         in scopes.get(n, ""))
    assert fb == pytest.approx(20e-9)
    assert trace.module_runs(e, lo, hi, "jit_train_step") == [90e-9]


def test_exposed_collectives_and_idle_gaps():
    e = synthetic()
    # the all-reduce runs 85-95; fusion.9 covers 85-89: 6 ns exposed
    assert trace.exposed_collective_s(e, 0, 100) == pytest.approx(6e-9)
    gaps = trace.idle_gaps(e, 0, 100)
    # gaps: 0-5 (5), 60-72 (12, under the batch upload), 82-85, 95-100
    assert gaps[0] == ["cb.batch_upload", pytest.approx(12e-9)]
    assert sorted(g[1] for g in gaps) == pytest.approx(
        sorted([5e-9, 12e-9, 3e-9, 5e-9]))
    top = dict(trace.top_ops(e, 0, 100))
    assert top["fusion"] == pytest.approx(44e-9)   # 7, 8, 9


def test_instruction_and_module_names():
    assert trace.instruction(
        "%fused_update_pallas.1 = (f32[8]) custom-call(f32[8] %p)") == \
        "fused_update_pallas.1"
    assert trace.module("jit_train_step(12829383356701286994)") == \
        "jit_train_step"
    text = ('  %fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
            'metadata={op_name="jit(train_step)/tel.optimizer_update/add" '
            'source_file="x.py"}\n'
            '  ROOT %tuple.3 = (f32[8]) tuple(%fusion.12)\n')
    assert trace.scope_map(text) == {
        "fusion.12": "jit(train_step)/tel.optimizer_update/add"}


@pytest.fixture(scope="module")
def recorded():
    doc = json.loads(gzip.open(DATA / "train_adam8_2steps.json.gz", "rt")
                     .read())
    return [Event(*e) for e in doc["events"]], doc["scopes"]


def test_recorded_step_splits_into_its_scopes(recorded):
    """Two steps of the 8-bit train step: leaf ops add up to the step
    program's own run time (a different line of the trace), and split
    into forward/backward, optimizer update and a small remainder."""
    events, scopes = recorded
    lo, hi = trace.window(events, "cb.window")
    runs = trace.module_runs(events, lo, hi, "jit_train_step")
    assert len(runs) == 2 and all(1.09 < r < 1.12 for r in runs)
    # the device never idles between steps: no host sync in the loop
    busy = trace.busy_s(events, lo, hi)
    assert busy / ((hi - lo) / 1e9) > 0.998
    assert busy == pytest.approx(sum(runs), rel=2e-3)
    every = trace.op_time_s(events, lo, hi, lambda n: True)
    assert every == pytest.approx(sum(runs), rel=2e-2)

    def scope(tag):
        return trace.op_time_s(events, lo, hi,
                               lambda n: tag in scopes.get(n, "")) / 2

    fb, opt = scope("tel.forward_backward"), scope("tel.optimizer_update")
    assert 0.60 < fb < 0.70 and 0.40 < opt < 0.47
    assert fb + opt > 0.97 * sum(runs) / 2
    kernel = trace.op_time_s(events, lo, hi, lambda n: n.startswith(
        "fused_update_pallas")) / 2
    assert 0.40 < kernel < 0.42 and kernel < opt
    assert trace.exposed_collective_s(events, lo, hi) == 0.0
    top = [name for name, _ in trace.top_ops(events, lo, hi, 3)]
    assert top[:2] == ["fusion", "fused_update_pallas"]
    gaps = trace.idle_gaps(events, lo, hi, 3)
    assert sum(g for _, g in gaps) < 0.002
