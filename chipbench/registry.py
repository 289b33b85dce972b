"""Find a cell's configuration, traffic mix, limits and metric readers by
the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own under the benchmark directory:

    configs/<config>.json      sizes as run, and the name of the reference
    traffic/<mix>.json         the generator's parameters, and its timed entry
    limits/<workload>.json     the limits of the numbers ``correct`` compares
    metrics/<metric>.py        a reader: record -> value or None

so a new cell needs new files and a new ``BENCHMARK.json`` entry only.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent
REPO_ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list        # metric entries of BENCHMARK.json for this cell
    per_layer: list


def load_bench(root: pathlib.Path = REPO_ROOT) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def resolve(workload: str, root: pathlib.Path = REPO_ROOT,
            bench_dir: pathlib.Path = BENCH_DIR) -> Cell:
    """The cell named ``workload``, with its files read."""
    bench = load_bench(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[w["config"]]
    config = json.loads((pathlib.Path(root) / centry["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads(
        (bench_dir / "limits" / f"{workload}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer)


def metric_reader(name: str, bench_dir: pathlib.Path = BENCH_DIR):
    """The ``read(record)`` function of ``metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
