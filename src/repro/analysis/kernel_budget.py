"""Pallas resource analyzer: per-tile VMEM bytes and grid alignment
(DESIGN.md §15b).

Every Pallas kernel in the repo tiles the flat block domain with static
BlockSpecs, so its per-grid-step VMEM footprint is a closed-form function
of ``(rows, block_size, bits, algo)`` — no compiler in the loop.  This
module mirrors those BlockSpec layouts byte-for-byte (the layouts are
quoted from kernels/fused_update.py, blockwise_quant.py,
blockwise_dequant.py, newton_schulz.py; a test pins the mirror against
the real specs), adds a scratch model for the in-kernel intermediates,
and checks the pipelined total against the backend VMEM budget.  A
second family of checks pins the grid alignment the partitioned
dispatch relies on: ``ArenaPartition.span_pad`` and every ``BucketPlan``
range must stay multiples of the kernel block grid (``rows``), or the
shard_map spans would split a Pallas tile across owners.

The table built by :func:`budget_table` is what ``benchmarks/run.py
--analyze`` records into BENCH_speed.json (VMEM headroom per kernel
config), and what ``python -m repro.analysis kernels`` gates CI on.

Unlike :mod:`repro.analysis.contracts` this module may import the kernel
modules (it needs ALGO_SPECS and the packing arithmetic); it is imported
explicitly by the CLI/tests, never by production modules.
"""
from __future__ import annotations

import dataclasses

from repro.analysis.contracts import AnalysisError
from repro.core.lowbit.packing import packed_width
from repro.kernels import ops as _ops  # noqa: F401 — anchors the kernels
# package import cycle (ops -> ref -> newton_schulz) at its usual root
# before the leaf modules are bound directly.
from repro.kernels import common as _kc
from repro.kernels import fused_update as _fu
from repro.kernels import newton_schulz as _ns

# Per-backend VMEM budget for one core's kernel working set.  TPU VMEM is
# ~16 MiB/core (accelerator guide); "interpret"/"jnp" paths have no real
# budget but are checked against the TPU number anyway — a tile that can
# never fit on the perf backend is a bug regardless of where CI runs.
VMEM_BUDGET_BYTES = {
    "tpu": 16 << 20,
}
DEFAULT_BACKEND = "tpu"

# Pallas double-buffers the HBM<->VMEM streams: while the compute units
# chew grid step i, the DMA engine prefetches step i+1's inputs and
# drains step i-1's outputs, so streamed blocks cost ~2x their size.
# Grid-invariant blocks (codebooks, the scalars vector) are fetched once.
PIPELINE_FACTOR = 2

_F32 = 4
_I32 = 4
_U8 = 1
# One codebook or boundary lane table (``common.table_spec``): a (2, 128)
# f32 VMEM block, which fills a whole (8, 128) f32 tile.
_SUBLANES = 8
TABLE_BYTES = _SUBLANES * _kc.LANES * _F32


@dataclasses.dataclass(frozen=True)
class TileBudget:
    """Per-grid-step VMEM bytes of one kernel configuration."""
    kernel: str
    config: dict
    streamed_in: int      # per-step input blocks (double-buffered)
    streamed_out: int     # per-step output blocks (double-buffered)
    invariant: int        # grid-invariant blocks (fetched once)
    scratch: int          # in-kernel intermediates (registers/VMEM temps)

    @property
    def total(self) -> int:
        return (PIPELINE_FACTOR * (self.streamed_in + self.streamed_out)
                + self.invariant + self.scratch)

    def fits(self, budget: int = VMEM_BUDGET_BYTES[DEFAULT_BACKEND]) -> bool:
        return self.total <= budget

    def headroom(self, budget: int = VMEM_BUDGET_BYTES[DEFAULT_BACKEND]
                 ) -> int:
        return budget - self.total

    def to_dict(self, budget: int = VMEM_BUDGET_BYTES[DEFAULT_BACKEND]
                ) -> dict:
        return {"kernel": self.kernel, **self.config,
                "streamed_in_bytes": self.streamed_in,
                "streamed_out_bytes": self.streamed_out,
                "invariant_bytes": self.invariant,
                "scratch_bytes": self.scratch,
                "total_bytes": self.total,
                "budget_bytes": budget,
                "headroom_bytes": self.headroom(budget),
                "fits": self.fits(budget)}


def _lookup_scratch(tile_elems: int) -> int:
    """The codebook search / lookup intermediates of kernels/common.py:
    one tile-sized result (i32 codes or f32 values) plus the gathered
    boundary or table entry of the lane-gather search."""
    return tile_elems * (_I32 + _F32)


def fused_update_tile(algo: str, *, rows: int = _kc.DEFAULT_ROWS,
                      block_size: int = 2048, bits_m: int = 8,
                      bits_r: int = 8, stochastic: bool = False
                      ) -> TileBudget:
    """VMEM bytes of one ``fused_update_pallas`` grid step — the exact
    in_specs/out_specs assembly of kernels/fused_update.py:484-546."""
    spec = _fu.ALGO_SPECS[algo]
    if spec.matrix:
        raise AnalysisError(
            f"{algo} is matrix-class; budget its NS chain with "
            f"newton_schulz_tiles()")
    two = spec.n_states == 2
    bsz = block_size
    w1 = packed_width(bsz, bits_m)
    w2 = packed_width(bsz, bits_r) if two else 0

    row = rows * bsz * _F32          # (rows, bsz) f32
    code1 = rows * w1 * _U8          # (rows, w1) u8
    code2 = rows * w2 * _U8
    one = rows * 1 * _F32            # (rows, 1) f32 / i32
    scal = _fu.N_SCALARS * _F32      # (N_SCALARS,) f32 in SMEM

    streamed_in = 2 * row + code1 + one            # p, g, codes_m, absmax_m
    if two:
        streamed_in += code2 + one                 # codes_r, absmax_r
    if stochastic:
        streamed_in += 2 * one                     # block_seeds, offsets
    if spec.needs_norms:
        streamed_in += one                         # tensor_scale slice
    invariant = scal + 2 * TABLE_BYTES             # scalars, qmap, bounds
    if two:
        invariant += 2 * TABLE_BYTES

    streamed_out = row + code1 + one               # p', codes_m', absmax_m'
    if two:
        streamed_out += code2 + one

    # Scratch: per-state unpack (i32) + decode (f32) for sub-byte slots,
    # the update intermediates (~2 row-sized f32 temps), the requant
    # search accumulators per state, and the stochastic uniforms.
    tile_elems = rows * bsz
    n_states = 2 if two else 1
    scratch = 2 * row                              # update temps
    scratch += n_states * tile_elems * (_I32 + _F32)   # unpack + decode
    scratch += n_states * _lookup_scratch(tile_elems)  # requant search
    if stochastic:
        scratch += n_states * tile_elems * _F32        # counter uniforms

    return TileBudget(
        kernel="fused_update", streamed_in=streamed_in,
        streamed_out=streamed_out, invariant=invariant, scratch=scratch,
        config={"algo": algo, "rows": rows, "block_size": bsz,
                "bits_m": bits_m, "bits_r": bits_r if two else None,
                "stochastic": stochastic})


def quantize_tile(*, rows: int = _kc.DEFAULT_ROWS, block_size: int = 2048
                  ) -> TileBudget:
    """One ``quantize_blockwise`` grid step (blockwise_quant.py): in
    (rows, bsz) f32 + the boundary lane table -> (rows, w) u8 + (rows, 1)
    f32."""
    bsz = block_size
    row = rows * bsz * _F32
    return TileBudget(
        kernel="blockwise_quant",
        streamed_in=row,
        streamed_out=rows * bsz * _U8 + rows * _F32,
        invariant=TABLE_BYTES,                      # boundaries
        scratch=_lookup_scratch(rows * bsz),
        config={"rows": rows, "block_size": bsz})


def dequantize_tile(*, rows: int = _kc.DEFAULT_ROWS, block_size: int = 2048
                    ) -> TileBudget:
    """One ``dequantize_blockwise`` grid step (blockwise_dequant.py): in
    (rows, bsz) u8 + (rows, 1) f32 + the codebook lane table ->
    (rows, bsz)."""
    bsz = block_size
    return TileBudget(
        kernel="blockwise_dequant",
        streamed_in=rows * bsz * _U8 + rows * _F32,
        streamed_out=rows * bsz * _F32,
        invariant=TABLE_BYTES,
        scratch=_lookup_scratch(rows * bsz),
        config={"rows": rows, "block_size": bsz})


def newton_schulz_tiles(m: int, *, tile_n: int = _ns.TILE_N) -> list:
    """The two NS pallas_calls per iteration (newton_schulz.py): the gram
    kernel streams one (m, tile_n) operand tile per grid step and
    accumulates into a grid-invariant (m, m) output; the apply kernel
    streams (m, tile_n) in and out against an invariant (m, m) factor.
    ``m`` is the padded small dimension (sublane multiple)."""
    mp = -(-m // _ns._SUBLANE) * _ns._SUBLANE
    gram = TileBudget(
        kernel="newton_schulz_gram",
        streamed_in=mp * tile_n * _F32,
        streamed_out=0,
        invariant=mp * mp * _F32,             # accumulator lives across grid
        scratch=mp * mp * _F32,               # the per-step partial product
        config={"m": mp, "tile_n": tile_n})
    apply_ = TileBudget(
        kernel="newton_schulz_apply",
        streamed_in=mp * tile_n * _F32,
        streamed_out=mp * tile_n * _F32,
        invariant=mp * mp * _F32,
        scratch=mp * tile_n * _F32,
        config={"m": mp, "tile_n": tile_n})
    return [gram, apply_]


def ns_max_m(*, tile_n: int = _ns.TILE_N,
             budget: int = VMEM_BUDGET_BYTES[DEFAULT_BACKEND]) -> int:
    """Largest (sublane-aligned) small dimension the NS kernels support
    within ``budget`` — the envelope of newton_schulz.py's "the small dim
    fits VMEM" assumption.  Matrix leaves beyond this need a tiled (m, m)
    accumulator the kernel does not implement; the audit pins the envelope
    so a config regression (or a budget model change) is caught statically."""
    m = _ns._SUBLANE
    while all(t.fits(budget) for t in
              newton_schulz_tiles(m + _ns._SUBLANE, tile_n=tile_n)):
        m += _ns._SUBLANE
    return m


# ------------------------------------------------------- grid alignment
def check_partition_plan(part, plan, grid: int) -> tuple:
    """Validate an (ArenaPartition, BucketPlan) pair against the block
    ``grid`` the dispatch was built on (``cfg.shard_multiple``): span
    starts and span_pad stay grid-aligned (a span boundary inside a
    storage-shard block would split whole-block ownership), spans cover
    exactly [0, total), and bucket ranges tile [0, span_pad) exactly with
    grid-aligned boundaries (the overlap schedule slices kernel inputs at
    these rows).  Takes the *built objects* so a regression in
    make_partition/make_buckets — or a hand-constructed bad plan — is
    caught, not just reproduced."""
    problems = []
    if part.span_pad % grid != 0:
        problems.append(f"span_pad {part.span_pad} not a multiple of "
                        f"grid={grid}")
    for start, length in part.spans:
        if start % grid != 0:
            problems.append(f"span start {start} misaligned to grid={grid}")
    lengths = sum(length for _, length in part.spans)
    if lengths != part.total:
        problems.append(f"spans cover {lengths} rows, total is {part.total}")
    if plan is not None:
        if plan.span_pad != part.span_pad:
            problems.append(f"plan span_pad {plan.span_pad} != partition "
                            f"span_pad {part.span_pad}")
        prev = 0
        for k0, k1 in plan.ranges:
            if k0 != prev:
                problems.append(f"bucket ranges not contiguous at {k0} "
                                f"(expected {prev})")
            if k1 <= k0:
                problems.append(f"empty/negative bucket range ({k0}, {k1})")
            if k0 % grid != 0:
                problems.append(f"bucket start {k0} misaligned to "
                                f"grid={grid}")
            if k1 % grid != 0 and k1 != part.span_pad:
                problems.append(f"bucket end {k1} misaligned to grid={grid}"
                                f" (span_pad={part.span_pad})")
            prev = k1
        if plan.ranges and prev != part.span_pad:
            problems.append(f"bucket ranges end at {prev}, span_pad is "
                            f"{part.span_pad}")
    ok = not problems
    return ok, ("grid-aligned" if ok else "; ".join(problems))


def check_grid_alignment(total: int, n_shards: int, n_buckets: int,
                         grid: int = _kc.DEFAULT_ROWS) -> tuple:
    """Build the partition/bucket plan exactly as the partitioned dispatch
    does (blockopt: make_partition/make_buckets on cfg.shard_multiple) and
    validate it with :func:`check_partition_plan`."""
    from repro.core.optim import base as _base
    part = _base.make_partition(total, n_shards, grid=grid)
    plan = _base.make_buckets(part, n_buckets, grid=grid)
    ok, detail = check_partition_plan(part, plan, grid)
    return ok, (f"partition(total={total}, shards={n_shards}, "
                f"buckets={n_buckets}, grid={grid}): {detail}")


# ------------------------------------------------------------- the table
def budget_table(*, rows: int = _kc.DEFAULT_ROWS, block_size: int = 2048,
                 budget: int = VMEM_BUDGET_BYTES[DEFAULT_BACKEND]) -> list:
    """VMEM budget rows for every registered element-wise fused-update
    config (each non-matrix algo x 8-bit and 4-bit momentum x stochastic
    on/off), the quant/dequant kernels, and representative NS sizes."""
    tiles = []
    for algo, spec in _fu.ALGO_SPECS.items():
        if spec.matrix:
            continue
        for bits_m in (8, 4):
            for stoch in (False, True):
                tiles.append(fused_update_tile(
                    algo, rows=rows, block_size=block_size, bits_m=bits_m,
                    stochastic=stoch))
    tiles.append(quantize_tile(rows=rows, block_size=block_size))
    tiles.append(dequantize_tile(rows=rows, block_size=block_size))
    # NS rows: the repo's representative matrix-leaf sizes plus the
    # envelope boundary (documentation rows; m=4096 does NOT fit — muon
    # leaves that large need a tiled accumulator, see ns_max_m()).
    for m in (256, 1024, 4096):
        tiles.extend(newton_schulz_tiles(m))
    return [t.to_dict(budget) for t in tiles]


def audit(*, rows: int = _kc.DEFAULT_ROWS, block_size: int = 2048,
          budget: int = VMEM_BUDGET_BYTES[DEFAULT_BACKEND]) -> list:
    """Run the full kernel-budget audit: every budget_table row must fit,
    and the partitioned dispatch's representative arena shapes must stay
    grid-aligned.  Returns (name, ok, detail) tuples."""
    results = []
    max_m = ns_max_m(budget=budget)
    for row in budget_table(rows=rows, block_size=block_size, budget=budget):
        cfg = {k: v for k, v in row.items()
               if k not in ("kernel", "fits") and not k.endswith("_bytes")}
        name = f"vmem:{row['kernel']}:{cfg}"
        detail = (f"{row['total_bytes']} B of {row['budget_bytes']} B "
                  f"({row['headroom_bytes']} B headroom)")
        if row["kernel"].startswith("newton_schulz") and row["m"] > max_m:
            # documentation row beyond the kernel's supported envelope
            results.append((name, True, detail + f" [beyond NS envelope "
                            f"m<={max_m}; informational]"))
            continue
        results.append((name, row["fits"], detail))
    # The NS envelope itself must cover the repo's matrix-leaf sizes: the
    # reduced configs orthogonalize up to d_model=1024 leaves.
    results.append((f"ns_envelope:max_m={max_m}", max_m >= 1024,
                    f"largest VMEM-resident NS small-dim is {max_m}, "
                    f"need >= 1024"))
    # Representative arena shapes: uneven totals, shard counts from the
    # config matrix, bucket counts from the overlap schedule.  The grid is
    # what production passes (cfg.shard_multiple == mesh size), so this
    # re-validates the make_partition/make_buckets contract the overlap
    # slicing depends on — coverage, contiguity, grid alignment.
    for total, shards, buckets, grid in ((1000, 4, 1, 4), (12345, 4, 2, 4),
                                         (8192, 8, 4, 8), (7, 4, 2, 4),
                                         (1000, 4, 2, rows)):
        ok, detail = check_grid_alignment(total, shards, buckets, grid=grid)
        results.append((f"grid:total={total},shards={shards},"
                        f"buckets={buckets},grid={grid}", ok, detail))
    return results
