"""A tiny benchmark directory for CPU tests: the paper's LM and
stablelm's block at toy widths, with the cells' own timed entries."""
from __future__ import annotations

import json
import pathlib
import shutil

from chipbench import registry

TINY = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
        "head_dim": 16, "d_ff": 128, "vocab_size": 256, "attn_chunk": 32}


def _config(name: str) -> dict:
    cfg = json.loads((registry.BENCH_DIR / "configs" / f"{name}.json")
                     .read_text())
    cfg.update(TINY)
    cfg["reduced"] = sorted(TINY)
    return cfg


def make(root: pathlib.Path, limits=None) -> pathlib.Path:
    """Write BENCHMARK.json and the files of three tiny cells under
    ``root``; returns the benchmark directory."""
    bench = root / "bench"
    for sub in ("configs", "traffic", "limits", "metrics"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    for m in (registry.BENCH_DIR / "metrics").glob("*.py"):
        shutil.copy(m, bench / "metrics" / m.name)
    for name in ("paper-lm-209m", "stablelm-1.6b"):
        (bench / "configs" / f"tiny-{name}.json").write_text(
            json.dumps(_config(name)))
    train = {"entry": "train", "optimizer": "adam8",
             "optimizer_kw": {"lr": 1e-3, "beta1": 0.9, "beta2": 0.999,
                              "eps": 1e-8, "weight_decay": 0.0},
             "grad_clip": 1.0, "global_batch": 8, "seq_len": 32, "ring": 4,
             "branching": 4, "chain_seed": 3, "ref_rows": 4,
             "trace_steps": 2}
    (bench / "traffic" / "tiny_adam8.json").write_text(json.dumps(train))
    (bench / "traffic" / "tiny_adam32.json").write_text(
        json.dumps(dict(train, optimizer="adam32")))
    serve = {"entry": "serve",
             "kv": {"page_size": 4, "n_pages": 64, "n_slots": 8,
                    "max_pages_per_seq": 12, "kv_bits": 8},
             "prompt_lens": [8, 16], "wave_requests": 6, "shape_seed": 7,
             "output_len": {"median": 6, "sigma": 0.7, "min": 2, "max": 12},
             "branching": 4, "chain_seed": 3, "check_requests": 3}
    (bench / "traffic" / "tiny_serve.json").write_text(json.dumps(serve))
    limits = limits or {}
    cells = [("tiny-train8", "tiny-paper-lm-209m", "tiny_adam8",
              {"loss": 4e-4, "grad": 4e-3, "update": 0.01}),
             ("tiny-train32", "tiny-paper-lm-209m", "tiny_adam32",
              {"loss": 4e-4, "grad": 4e-3, "update": 0.05}),
             ("tiny-serve", "tiny-stablelm-1.6b", "tiny_serve",
              {"logit_gap": 0.03})]
    for cell, _, _, lim in cells:
        (bench / "limits" / f"{cell}.json").write_text(
            json.dumps(limits.get(cell, lim)))
    real = registry.load_bench()
    doc = dict(real)
    doc["configs"] = [{"name": c, "source": "toy widths for CPU tests",
                       "file": f"bench/configs/{c}.json", "reduced": [],
                       "why": "test"}
                      for c in ("tiny-paper-lm-209m", "tiny-stablelm-1.6b")]
    doc["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                         "why": "test"} for n, c, t, _ in cells]
    train_cells = ["tiny-train8", "tiny-train32"]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            serving = "serve" in m.get("moves", m["name"])
            m["workloads"] = ["tiny-serve"] if serving else train_cells
    (root / "BENCHMARK.json").write_text(json.dumps(doc, indent=1))
    return bench
