"""Whole runs of tiny cells on the CPU (the harness's look for a chip
skipped): a cell added by files alone runs and reports, the timed path
broken underneath turns ``correct`` false, and the control fails."""
import json
import time

import jax
import pytest

from chipbench import control, registry, run
from chipbench.entries import serve as S
from chipbench.entries import train as T

SEED = 2 ** 31 + 11          # wider than a signed 32-bit int


def _run(tiny_bench, workload, trace=False, seconds=0.5):
    root, bench = tiny_bench
    line, _ = run.run_cell(workload, SEED, seconds, trace,
                           require_tpu=False, root=root, bench_dir=bench,
                           t_start=time.perf_counter())
    return line


def test_cell_from_files_alone(tiny_bench):
    """The tiny cells exist only as files and BENCHMARK.json entries in a
    directory of their own, with a per-layer metric of their own."""
    root, bench = tiny_bench
    (bench / "metrics" / "steps_traced.py").write_text(
        '"""Train steps in the traced window."""\n'
        'LAYER = "train step"\nUNIT = "steps"\nBETTER = "higher"\n'
        'MOVES = "train_tokens_per_s"\n\n\n'
        'def read(record):\n'
        '    return float(record["steps"])\n')
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["per_layer"].append({
        "name": "steps_traced", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "train_tokens_per_s", "workloads": ["tiny-train8"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    line = _run(tiny_bench, "tiny-train8")
    assert line["correct"] is True
    assert set(line["metrics"]) == {"train_tokens_per_s", "train_hbm_gib",
                                    "setup_s"}
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == {"loss", "grad", "update"}
    traced = _run(tiny_bench, "tiny-train8", trace=True)
    assert traced["correct"] is True
    assert traced["metrics"]["steps_traced"]["value"] == 2.0
    assert traced["device"]["window_s"] > 0


def test_serve_cell_runs_and_is_correct(tiny_bench):
    line = _run(tiny_bench, "tiny-serve")
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 6 and line["failed"] == 0
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def _broken_train(monkeypatch, fault):
    real = T.build

    def build(cell, seed):
        from repro.train import loop as TL
        from chipbench import modelcfg
        opt, step, state, ring, key = real(cell, seed)
        inner = TL.make_train_step(modelcfg.program_config(cell.config),
                                   opt, TL.TrainHyper())

        def broken(state, batch):
            if fault == "state_unchanged":
                return state, inner(state, batch)[1]
            half = batch["tokens"][: batch["tokens"].shape[0] // 2]
            return inner(state, {"tokens": half})

        return (opt, jax.jit(broken).lower(state, {"tokens": ring[0]})
                .compile(), state, ring, key)

    monkeypatch.setattr(T, "build", build)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_train_step_is_not_correct(tiny_bench, monkeypatch, fault):
    _broken_train(monkeypatch, fault)
    line = _run(tiny_bench, "tiny-train8")
    assert line["correct"] is False
    failing = [k for k, c in line["checks"].items()
               if not c["value"] <= c["limit"]]
    assert failing, line["checks"]


def test_altered_token_is_not_correct(tiny_bench, monkeypatch):
    real = S.build

    def build(cell, seed):
        engine, params = real(cell, seed)
        serve = engine.serve

        def altered(requests):
            out = serve(requests)
            for rid, toks in out.items():       # every request's middle
                toks = toks.copy()              # token, where produced
                toks[len(toks) // 2] = (toks[len(toks) // 2] + 1) % \
                    cell.config["vocab_size"]
                out[rid] = toks
            return out

        engine.serve = altered
        return engine, params

    monkeypatch.setattr(S, "build", build)
    line = _run(tiny_bench, "tiny-serve")
    assert line["correct"] is False
    assert line["checks"]["logit_gap"]["value"] > \
        line["checks"]["logit_gap"]["limit"]


def test_control_fails_the_tiny_limits(tiny_bench):
    """The control (fp8 reference in the program's place) and the planted
    faults each fail at least one of the tiny cells' limits."""
    root, bench = tiny_bench
    for workload in ("tiny-train8", "tiny-serve"):
        cell = registry.resolve(workload, root, bench)
        fn = control.train_readings if cell.traffic["entry"] == "train" \
            else control.serve_readings
        readings = fn(cell, SEED, program=True, controls=True)
        assert readings.pop("left_out_of_update", []) == []
        for name, numbers in readings.items():
            over = [k for k, v in numbers.items() if v > cell.limits[k]]
            if name == "program":
                assert not over, (workload, numbers)
            else:
                assert over, (workload, name, numbers)


def test_no_chip_exits_without_a_result(capsys):
    """On the CPU the harness's look for a chip ends the run: exit code 2,
    no result line."""
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", "train-lm209m-adam8", "--seed", "1",
                  "--seconds", "1"])
    assert exc.value.code == run.NO_CHIP_EXIT
    assert "{" not in capsys.readouterr().out
