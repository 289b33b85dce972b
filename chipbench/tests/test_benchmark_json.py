"""BENCHMARK.json against the benchmark's contract, and every name in it
against the files that the harness finds by that name."""
import json
import re

import pytest

from chipbench import modelcfg, registry

DOC = registry.load_bench()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert DOC["paths"] == ["chipbench"]
    assert 1 <= DOC["run_seconds"] <= 51
    assert len(DOC["command"]) <= 32 and all(_line(w) for w in DOC["command"])
    assert len(json.dumps(DOC)) < 64 * 1024


def test_entries():
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for section, want in keys.items():
        names = [e["name"] for e in DOC[section]]
        assert len(names) == len(set(names))
        for e in DOC[section]:
            assert set(e) - {"workloads"} == want, e
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
    for e in DOC["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    assert any(e["name"] == "setup_s" for e in DOC["end_to_end"])


def test_each_cell_has_its_files_and_metrics():
    cells = {w["name"]: w for w in DOC["workloads"]}
    configs = {c["name"] for c in DOC["configs"]}
    pairs = {(w["config"], w["traffic"]) for w in cells.values()}
    assert len(pairs) == len(cells)
    assert configs == {w["config"] for w in cells.values()}
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 2)
    for name, w in cells.items():
        assert _line(w["why"]) and w["chips"] in (1, 4)
        cell = registry.resolve(name)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert cell.limits and all(v > 0 for v in cell.limits.values())
        for m in cell.per_layer:
            reader = registry.metric_reader(m["name"])
            assert reader.LAYER == m["layer"] and reader.UNIT == m["unit"]
            assert reader.MOVES == m["moves"] and reader.BETTER == m["better"]
            assert m["moves"] in {e["name"] for e in cell.end_to_end}


@pytest.mark.parametrize("entry", DOC["configs"], ids=lambda c: c["name"])
def test_config_files_match_the_registered_configs(entry):
    assert entry["file"].startswith("chipbench/configs/")
    cfg = json.loads((registry.REPO_ROOT / entry["file"]).read_text())
    assert cfg["reduced"] == entry["reduced"]
    assert _line(entry["source"]) and _line(entry["why"])
    modelcfg.program_config(cfg)        # raises on any unlisted change
