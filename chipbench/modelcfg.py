"""A configuration file -> the program's ``ModelConfig``.

The file holds the configuration as it is run.  Keys that are fields of
the program's ``ModelConfig`` are the model; the rest (``arch``,
``reference``, ``source``, ``reduced``, ``assumed``, ``deployment``,
``departs``) are the benchmark's.  Every model key not listed in ``reduced`` must equal
the registered configuration's value."""
from __future__ import annotations

import dataclasses

META_KEYS = ("arch", "reference", "source", "reduced", "assumed",
             "deployment", "departs")


def model_keys(config: dict) -> dict:
    return {k: v for k, v in config.items() if k not in META_KEYS}


def program_config(config: dict):
    from repro.configs import base as cfgs
    base = cfgs.get_config(config["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    keys = model_keys(config)
    unknown = sorted(set(keys) - fields)
    if unknown:
        raise KeyError(f"{config['arch']}: keys {unknown} are not fields of "
                       f"the program's ModelConfig")
    over = {k: tuple(v) if isinstance(v, list) else v
            for k, v in keys.items()}
    reduced = set(config.get("reduced", ()))
    drift = sorted(k for k, v in over.items()
                   if k not in reduced and getattr(base, k) != v)
    if drift:
        raise ValueError(f"{config['arch']}: {drift} differ from the "
                         f"registered configuration but are not in 'reduced'")
    return dataclasses.replace(base, **over)
