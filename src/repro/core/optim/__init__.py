"""8-bit optimizers (paper core) + 32-bit baselines + matrix optimizers.

Factory usage (the "two-line change" of the paper):

    opt = make_optimizer("adam8", lr=1e-3)      # instead of "adam32"
    state = opt.init(params)
    params, state = opt.apply(grads, state)

``make_optimizer`` is the single construction entry point: it accepts a
registered *name* ("adam8", "muon8", "adafactor32", ...) or a ready
*config object* (``OptimConfig`` / ``AdafactorConfig``) and dispatches to
the right engine class (``Block8bitOptimizer``, ``MuonOptimizer``,
``Adafactor``) — train/launch/serve construct every optimizer through it
instead of per-module conditionals.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Union

import jax

from repro.core.optim.adafactor import Adafactor, AdafactorConfig
from repro.core.optim.base import (ALGOS, ArenaPartition, FlatSegment,
                                   Full32Leaf, OptimConfig, Pool32Arena,
                                   Pool32Leaf, PooledQuantLeaf, Quant8Leaf,
                                   QuantArena, QuantSegment,
                                   default_override_32bit, make_partition)
from repro.core.optim.blockopt import (Block8bitOptimizer, OptState,
                                       repool_like, unpool_state)
from repro.core.optim.muon import MuonOptimizer
from repro.errors import ConfigError

# name: (algo, bits) — every registered algorithm gets an "<algo>8" and an
# "<algo>32" name, so new algorithms are CLI-runnable without extra wiring.
_NAMES = {f"{algo}{bits}": (algo, bits) for algo in ALGOS for bits in (8, 32)}


def optimizer_names() -> list:
    """Every constructible optimizer name (quickstart/launch CLI choices)."""
    return sorted(_NAMES) + ["adafactor32"]


def _from_config(cfg, override_32bit=None, mesh=None):
    """Config object -> engine instance (the one dispatch point)."""
    if isinstance(cfg, AdafactorConfig):
        return Adafactor(cfg)
    if not isinstance(cfg, OptimConfig):
        raise ConfigError(f"expected OptimConfig or AdafactorConfig, got "
                          f"{type(cfg).__name__}")
    if cfg.algo == "muon":
        return MuonOptimizer(cfg, override_32bit=override_32bit, mesh=mesh)
    return Block8bitOptimizer(cfg, override_32bit=override_32bit, mesh=mesh)


def _check_auto_axes(mesh) -> None:
    auto = jax.sharding.AxisType.Auto
    bad = {name: t for name, t in zip(mesh.axis_names,
                                      getattr(mesh, "axis_types", ()))
           if t != auto}
    if bad:
        raise ConfigError(
            f"mesh axes {bad} are not AxisType.Auto; the optimizer's "
            f"block-domain reshapes need Auto axes — build the mesh with "
            f"repro.launch.mesh.make_mesh")


def make_optimizer(name_or_config: Union[str, OptimConfig, AdafactorConfig],
                   override_32bit: Optional[Callable[[str], bool]] = None,
                   mesh=None,
                   **kwargs):
    """Build an optimizer from a name or a config object.

    Names: ``adafactor32`` or ``<algo>8``/``<algo>32`` for any registered
    algorithm (adam/adamw/momentum/lamb/lars/adagrad/muon).  Config
    objects (``OptimConfig``/``AdafactorConfig``) construct directly —
    ``**kwargs`` are applied as ``dataclasses.replace`` overrides.

    ``override_32bit``: path predicate forcing 32-bit state for matching
    leaves (defaults to the paper's stable-embedding rule when quantized
    state is requested; pass ``lambda p: False`` to disable).  For muon the
    override additionally routes matched 2-D leaves to the element-wise
    adamw fallback (DESIGN.md §11) — Muon's usual embedding/head exclusion.

    Sub-byte state storage (DESIGN.md §9) is a config field:
    ``make_optimizer("adam8", state_bits=(4, 8))`` stores a packed 4-bit
    first moment and an 8-bit second moment; the same knob packs Muon's
    matrix momentum (``make_optimizer("muon8", state_bits=(4, 8))``).

    ``mesh``: device mesh for the partitioned (ZeRO-1) dispatch's
    shard_map path (DESIGN.md §12).  When the mesh has the
    ``cfg.partition_axes`` ("data"; "pod,data" on multi-pod meshes) with
    a combined size > 1 and ``partition_shards`` was left at its
    default, the shard count is derived from the mesh — so partitioning
    turns on automatically on data-parallel meshes, and
    ``partition=False`` opts out.  The mesh's axes must be Auto (build it
    with ``repro.launch.mesh.make_mesh``): under Explicit axes the
    block-domain reshapes cannot be typed, so such a mesh is refused."""
    if mesh is not None:
        _check_auto_axes(mesh)
    if isinstance(name_or_config, (OptimConfig, AdafactorConfig)):
        cfg = name_or_config
        if kwargs:
            cfg = dataclasses.replace(cfg, **kwargs)
        if isinstance(cfg, OptimConfig) and mesh is not None \
                and cfg.partition_shards == 1:
            names = getattr(mesh, "axis_names", ())
            axes = cfg.partition_axes
            if axes and all(a in names for a in axes):
                size = 1
                for a in axes:
                    size *= int(mesh.shape[a])
                cfg = dataclasses.replace(cfg, partition_shards=size)
        if isinstance(cfg, OptimConfig) and override_32bit is None \
                and (cfg.bits == 8 or cfg.algo == "muon"):
            # For muon the override doubles as the algorithm routing
            # (matched 2-D leaves run adamw, DESIGN.md §11), so the
            # embedding exclusion applies to the fp32 baseline too —
            # muon32 and muon8 must route identically to be comparable.
            override_32bit = default_override_32bit
        return _from_config(cfg, override_32bit, mesh=mesh)
    name = name_or_config
    if name == "adafactor32":
        fields = {f.name for f in dataclasses.fields(AdafactorConfig)}
        return _from_config(AdafactorConfig(
            **{k: v for k, v in kwargs.items() if k in fields}))
    if name not in _NAMES:
        raise ValueError(f"unknown optimizer '{name}'; have "
                         f"{optimizer_names()}")
    algo, bits = _NAMES[name]
    return make_optimizer(OptimConfig(algo=algo, bits=bits, **kwargs),
                          override_32bit=override_32bit, mesh=mesh)


__all__ = [
    "Adafactor", "AdafactorConfig", "ArenaPartition", "Block8bitOptimizer",
    "FlatSegment", "Full32Leaf", "MuonOptimizer", "OptimConfig", "OptState",
    "Pool32Arena", "Pool32Leaf", "PooledQuantLeaf", "Quant8Leaf",
    "QuantArena", "QuantSegment", "default_override_32bit",
    "make_optimizer", "make_partition", "optimizer_names", "repool_like",
    "unpool_state",
]
