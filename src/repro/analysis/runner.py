"""Config-matrix lowering builder + contract evaluator (DESIGN.md §15a).

Builds every lowering the registered contracts run on — jitted train
steps across the {pooled, partitioned, partitioned+ZeRO-2} x algo x
state-bits matrix, bare fused-update lowerings per (algo, bits), and the
knob pairs (telemetry_every 0 vs N, overlap_buckets 1 vs K, partition
on/off) — then evaluates :mod:`repro.analysis.contracts` over them.
Nothing executes: every artifact is ``jax.jit(...).lower(...)`` text,
so the whole audit runs on the CPU host in seconds.

Matrix notes:

  * ``percentile_clipping=95`` is set in partitioned cells so the §12
    replication pins appear for every algo (percentile_clip pins each
    grad leaf only when the config is partition-active).
  * The multi-device cells need >= 4 devices;
    ``python -m repro.analysis`` forces 4 host devices via XLA_FLAGS
    before importing jax.  Under fewer devices those cells are skipped
    with a notice (and the audit fails unless ``allow_skips``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

from repro.analysis.contracts import (AnalysisError, ContractResult,
                                      Lowering, contracts_for, evaluate)


@dataclasses.dataclass(frozen=True)
class Cell:
    """One config-matrix point (static description; contracts read it)."""
    name: str
    algo: str                  # optimizer name for make_optimizer
    state_bits: tuple          # (bits_m, bits_r)
    partition: int = 1         # partition_shards (1 = pooled, unsharded)
    shard_grads: bool = False  # ZeRO-2 grad accumulation
    overlap_buckets: int = 1


# The audited matrix: one pooled cell and two partitioned cells per
# (algo, bits) point.  adamw exercises the 2-state element-wise family,
# muon the matrix-class path; (4, 8) rides the sub-byte packing.
def default_cells() -> list:
    cells = []
    for algo in ("adamw8", "muon8"):
        for bits in ((8, 8), (4, 8)):
            tag = f"{algo}-b{bits[0]}{bits[1]}"
            cells.append(Cell(f"{tag}-pooled", algo, bits))
            cells.append(Cell(f"{tag}-part4", algo, bits, partition=4))
            cells.append(Cell(f"{tag}-part4-zero2", algo, bits,
                              partition=4, shard_grads=True,
                              overlap_buckets=2))
    return cells


@functools.lru_cache(maxsize=1)
def _harness():
    """The tiny model/pipeline the matrix lowers (built once)."""
    import jax.numpy as jnp
    from repro.configs import base
    from repro.data.pipeline import DataConfig, SyntheticLMPipeline

    cfg = base.reduced(base.get_config("paper-lm-209m"), d_model=64,
                       n_layers=2, vocab_size=128)
    pipe = SyntheticLMPipeline(DataConfig(vocab_size=128, seq_len=32,
                                          global_batch=8))
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    return cfg, batch


def _mesh(n: int):
    import jax
    if jax.device_count() < n:
        return None
    from repro.launch.mesh import make_mesh
    return make_mesh((n,), ("data",))


def _make_opt(cell: Cell, **overrides):
    from repro.core.optim import make_optimizer
    kw = dict(lr=5e-3, min_8bit_size=1024, state_bits=cell.state_bits)
    if cell.partition > 1:
        mesh = _mesh(cell.partition)
        if mesh is None:
            return None
        kw.update(mesh=mesh, percentile_clipping=95,
                  shard_grads=cell.shard_grads,
                  overlap_buckets=cell.overlap_buckets)
    kw.update(overrides)
    return make_optimizer(cell.algo, **kw)


def lower_step(cell: Cell, **overrides) -> Optional[Lowering]:
    """Lowered jitted train step for ``cell`` (None = needs more devices)."""
    import jax
    from repro.train import loop as L
    cfg, batch = _harness()
    opt = _make_opt(cell, **overrides)
    if opt is None:
        return None
    state, _ = L.init_train_state(cfg, opt, jax.random.PRNGKey(0))
    low = L.jit_train_step(cfg, opt).lower(state, batch)
    tag = "".join(f"-{k}{v}" for k, v in sorted(overrides.items()))
    return Lowering(name=f"step:{cell.name}{tag}", text=low.as_text())


def lower_update(algo: str, bits_m: int = 8) -> Lowering:
    """Bare fused-update lowering per (algo, bits) — the 'update' scope
    subject.  Uses impl='jnp' (the XLA oracle): the dtype/accumulation
    contracts audit the math's lowering, which the CPU host can build."""
    import jax
    import jax.numpy as jnp
    from repro.core import qmap as qmap_lib
    from repro.core.lowbit import PackedCodes, packed_width
    from repro.kernels import ops

    nb, bsz = 8, 256
    qm = jnp.asarray(qmap_lib.dynamic_map(signed=True, bits=bits_m))
    qr = jnp.asarray(qmap_lib.dynamic_map(signed=False))
    w = packed_width(bsz, bits_m)

    if algo == "muon":
        shape = (32, 64)
        p = jnp.zeros(shape, jnp.float32)
        g = jnp.zeros(shape, jnp.bfloat16)
    else:
        p = jnp.zeros((nb, bsz), jnp.float32)
        g = jnp.zeros((nb, bsz), jnp.float32)
    cm = jnp.zeros((nb, w), jnp.uint8)
    if bits_m != 8:
        cm = PackedCodes(cm, bits_m, bsz)
    am = jnp.zeros((nb,), jnp.float32)
    two = ops._fu.ALGO_SPECS[algo].n_states == 2
    cr = jnp.zeros((nb, bsz), jnp.uint8) if two else None
    ar = jnp.zeros((nb,), jnp.float32) if two else None

    def update(p, g, cm, am, cr, ar):
        return ops.fused_update(algo, p, g, cm, am, cr, ar, qm,
                                qr if two else None, lr=1e-3, impl="jnp")

    low = jax.jit(update).lower(p, g, cm, am, cr, ar)
    return Lowering(name=f"update:{algo}-b{bits_m}", text=low.as_text())


def lower_serve(kv_bits: int = 8) -> Lowering:
    """Lowered jitted paged decode step (the 'serve' scope subject): tiny
    dense config, donated cache pytree, page table + positions as inputs
    (DESIGN.md §17)."""
    import jax
    import jax.numpy as jnp
    from repro.models import layers as mlayers
    from repro.models import model as mm

    cfg, _ = _harness()
    params, _ = mm.init_model(cfg, jax.random.PRNGKey(0))
    n_slots, n_pages, page = 4, 16, 8
    caches = mm.init_paged_cache(cfg, n_slots, n_pages, page, kv_bits)
    paged = mlayers.PagedContext(
        jnp.zeros((n_slots, 4), jnp.int32),
        jnp.zeros((n_slots,), jnp.int32), impl="jnp")
    tok = jnp.zeros((n_slots, 1), jnp.int32)

    def step(params, token, caches, paged):
        return mm.paged_decode_step(cfg, params, token, caches, paged)

    low = jax.jit(step, donate_argnums=(2,)).lower(params, tok, caches,
                                                   paged)
    return Lowering(name=f"serve:decode-b{kv_bits}", text=low.as_text())


def _pair_cells(cells: list) -> dict:
    """Pick the matrix cells the knob-pair contracts run on."""
    by_name = {c.name: c for c in cells}
    return {
        # telemetry pair: pooled adamw (byte-equality needs an otherwise
        # identical config)
        "pair:telemetry": by_name.get("adamw8-b88-pooled"),
        # overlap pair: the ZeRO-2 cell (overlap_buckets only matters there)
        "pair:overlap": by_name.get("adamw8-b88-part4-zero2"),
        # partition pair: partitioned vs pooled adamw
        "pair:partition": by_name.get("adamw8-b88-part4"),
        # sentinel pair (§16): pooled adamw — off / explicit-off /
        # on-but-idle lowerings
        "pair:sentinel": by_name.get("adamw8-b88-pooled"),
    }


def run_contracts(cells: Optional[list] = None, *,
                  allow_skips: bool = False, log=print) -> list:
    """Evaluate every registered contract over the matrix.  Returns the
    ContractResult list; raises AnalysisError if multi-device cells had
    to be skipped and ``allow_skips`` is False."""
    # Importing the protected modules registers their contracts.
    import repro.kernels.ops  # noqa: F401
    import repro.serve.kvcache  # noqa: F401
    import repro.sharding.rules  # noqa: F401
    import repro.train.loop  # noqa: F401

    cells = default_cells() if cells is None else cells
    results: list = []
    skipped: list = []

    step_contracts = contracts_for("step")
    for cell in cells:
        low = lower_step(cell)
        if low is None:
            skipped.append(cell.name)
            continue
        for spec in step_contracts:
            r = evaluate(spec, low, cell)
            if r is not None:
                results.append(r)
                log(str(r))

    update_contracts = contracts_for("update")
    for algo in ("adamw", "muon"):
        for bits_m in (8, 4):
            low = lower_update(algo, bits_m)
            cell = Cell(low.name, algo, (bits_m, 8))
            for spec in update_contracts:
                r = evaluate(spec, low, cell)
                if r is not None:
                    results.append(r)
                    log(str(r))

    serve_contracts = contracts_for("serve")
    for kv_bits in (8, 4):
        low = lower_serve(kv_bits)
        cell = Cell(low.name, "serve", (kv_bits,))
        for spec in serve_contracts:
            r = evaluate(spec, low, cell)
            if r is not None:
                results.append(r)
                log(str(r))

    for scope, cell in _pair_cells(cells).items():
        if cell is None:
            continue
        specs = contracts_for(scope)
        if not specs:
            continue
        if scope == "pair:telemetry":
            pair = {n: lower_step(cell, telemetry_every=n) for n in (0, 2)}
        elif scope == "pair:overlap":
            pair = {n: lower_step(cell, overlap_buckets=n) for n in (1, 2)}
        elif scope == "pair:sentinel":
            # off (field default) vs explicit off must be byte-identical;
            # "on" only feeds the sentinel_invariant alias comparison.
            pair = {"off": lower_step(cell),
                    "off_explicit": lower_step(cell, sentinel=False),
                    "on": lower_step(cell, sentinel=True)}
        else:  # pair:partition — the pooled twin drops mesh/partitioning
            on = lower_step(cell)
            off = lower_step(dataclasses.replace(
                cell, name=cell.name + "-off", partition=1,
                shard_grads=False, overlap_buckets=1))
            pair = {"on": on, "off": off}
        if any(v is None for v in pair.values()):
            skipped.append(f"{scope}:{cell.name}")
            continue
        for spec in specs:
            r = evaluate(spec, pair, cell)
            if r is not None:
                results.append(r)
                log(str(r))

    if skipped and not allow_skips:
        raise AnalysisError(
            f"matrix cells skipped (need >= 4 devices; run via `python -m "
            f"repro.analysis`, which forces host devices): {skipped}")
    if skipped:
        log(f"skipped cells: {skipped}")
    return results


def failures(results: list) -> list:
    return [r for r in results if not r.ok]
