"""Pallas TPU kernel family: fused k-bit optimizer update, all algorithms.

One generic kernel builder, parameterized by a static :class:`AlgoSpec`
(update math, one-vs-two states, signedness, per-tensor norm needs), covers
adam / adamw / momentum / lamb / lars / adagrad.  Each grid step streams one
tile of the flat block domain HBM -> VMEM, dequantizes the quantized state,
runs the 32-bit update math in registers, and requantizes with per-block
absmax — the paper's §2 procedure in a single HBM pass per state tensor
(DESIGN.md §3).

State bitwidth is a per-slot static parameter (``bits_m`` / ``bits_r`` ∈
{4, 5, 6, 8}; DESIGN.md §9): sub-byte codes arrive bit-packed as
``(n_blocks, B*bits/8)`` uint8 words and are unpacked *inside* the kernel
(``repro.core.lowbit.unpack_codes`` — broadcast shifts, no gathers), so the
fused path streams only packed bytes through HBM and never materializes an
unpacked code tensor.  Requantized codes are re-packed in VMEM before the
store.  8-bit slots skip both steps and keep the legacy layout bit-exactly.

Extras fused into the same pass:

  * **stochastic rounding** — counter-based PRNG evaluated on the VPU
    (``common.hash_uniform``); no extra dequant/requant round trip and no
    host randomness, so restarts are bit-exact.
  * **gradient scaling** — the percentile-clipping ``gnorm_scale`` is a
    scalar multiplied into g in-kernel (bitsandbytes-style, DESIGN.md §7).

LAMB/LARS need per-tensor norms, which are global reductions and cannot be
fused into one block-local pass.  They get a *norm prologue*: a first grid
pass emits per-**block** partial sums of ||p||^2 / ||g||^2 / ||u||^2, the
XLA side finalizes them per *segment* (a contiguous block range belonging
to one logical tensor — the whole input by default, one range per pooled
leaf under the pooled dispatch, DESIGN.md §10) into a per-block
trust-ratio vector the main kernel streams like a second absmax (so
LAMB/LARS cost two passes instead of the jnp fallback's 3-4).

The pooled dispatch (DESIGN.md §10) batches many parameter leaves into one
arena, so per-leaf identity enters the kernel as three extra per-block
inputs/statics: ``block_seeds`` (each block's stochastic-rounding seed —
the seed of the leaf it belongs to), ``block_offsets`` (each block's index
*within its leaf*, so element indices for the counter-based PRNG are
leaf-local), and the static ``segments``.  With the defaults (constant
seed, ``arange`` offsets, one segment) the kernel is bit-identical to the
historical single-tensor behaviour.

``repro.kernels.ops`` registers these builders under ``(algo, "pallas")``
and ``(algo, "interpret")``; the matching jnp oracle lives in ``ref.py``
under ``(algo, "jnp")`` and shares :func:`update_math` /
:func:`tensor_scale_from_norms` with the kernels, so parity holds by
construction.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.lowbit import pack_codes, packed_width, unpack_codes
from repro.kernels import common

# Scalar vector layout (an SMEM operand of every kernel; ``scalars_vector``
# builds it).  Slot 7 holds trust_coeff on entry to fused_update_pallas;
# norm-needing algorithms (lamb/lars) consume the finalized per-block
# tensor_scale via a dedicated (n_blocks, 1) input instead, and the slot is
# rewritten to 1.0 before the main kernel runs.  c1/c2 are the adam bias
# corrections (``update_scalars``), precomputed outside the kernel
# because Mosaic has no scalar ``pow``.
SCALAR_SLOTS = ("lr", "beta1", "beta2", "eps", "weight_decay", "step",
                "gnorm_scale", "tensor_scale", "c1", "c2")
N_SCALARS = len(SCALAR_SLOTS)


@dataclasses.dataclass(frozen=True)
class AlgoSpec:
    """Static description of one optimizer algorithm for the kernel builder.

    name          : algorithm key ("adam", ...)
    n_states      : 1 (momentum/lars/adagrad/muon) or 2 (adam/adamw/lamb)
    state1_signed : first state uses the signed codebook (False: adagrad's
                    strictly-positive accumulator uses the unsigned map)
    norm_kind     : "" (block-local), "lamb" (needs ||p||, ||update||) or
                    "lars" (needs ||p||, ||g||) — selects the norm prologue
    matrix        : matrix-class algorithm (muon): the update consumes the
                    leaf in its 2-D *param shape* (Newton–Schulz matmuls,
                    kernels/newton_schulz.py) while the quantized state
                    stays in the flat block domain; ops.fused_update takes
                    matrix-shaped p/g and the engine dispatches such
                    leaves per-leaf, never through a pooled arena
                    (DESIGN.md §11).
    """
    name: str
    n_states: int
    state1_signed: bool
    norm_kind: str = ""
    matrix: bool = False

    @property
    def needs_norms(self) -> bool:
        return self.norm_kind != ""


ALGO_SPECS: dict[str, AlgoSpec] = {
    "adam":     AlgoSpec("adam", 2, True),
    "adamw":    AlgoSpec("adamw", 2, True),
    "lamb":     AlgoSpec("lamb", 2, True, norm_kind="lamb"),
    "momentum": AlgoSpec("momentum", 1, True),
    "lars":     AlgoSpec("lars", 1, True, norm_kind="lars"),
    "adagrad":  AlgoSpec("adagrad", 1, False),
    "muon":     AlgoSpec("muon", 1, True, matrix=True),
}


class FusedUpdateResult(NamedTuple):
    """Output of one fused update in the flat block domain.

    ``health`` is the optional numerics-sentinel output (DESIGN.md §16):
    per-block f32 counts ``(n_blocks, N_HEALTH)`` in :data:`HEALTH_SLOTS`
    order, present iff the dispatch ran with ``sentinel=True``.  A ``None``
    leaf vanishes in pytree flattening, so sentinel-off results (and their
    lowerings) are unchanged by the field's existence."""
    p: jax.Array
    codes_m: jax.Array
    absmax_m: jax.Array
    codes_r: Optional[jax.Array]
    absmax_r: Optional[jax.Array]
    health: Optional[jax.Array] = None


# ------------------------------------------------- numerics sentinel (§16)
# Slot layout of the per-block health counts the sentinel emits.  Counts
# are integer-valued f32 (exact addition in any order up to 2^24), so the
# Pallas tiles, the jnp oracle, per-span shard_map pieces and their
# concatenation/summation all agree bit-exactly.
HEALTH_SLOTS = (
    "nonfinite_grad",        # nonfinite entries in the incoming (raw) grad
    "nonfinite_update",      # nonfinite entries in the updated master
    "nonfinite_absmax_m",    # nonfinite new per-block absmax, state 1
    "nonfinite_absmax_r",    # nonfinite new per-block absmax, state 2
    "edge_hits_m",           # requantized state-1 codes at a codebook edge
    "edge_hits_r",           # requantized state-2 codes at a codebook edge
    "absmax_overflow_m",     # new state-1 absmax past the overflow guard
    "absmax_overflow_r",     # new state-2 absmax past the overflow guard
)
N_HEALTH = len(HEALTH_SLOTS)
# Norm-prologue partials share the (rows, N_HEALTH) tile shape.
N_PARTIALS = N_HEALTH

# f32 max is ~3.4e38; an absmax past 1e30 means squaring/scale math on the
# dequantized state is about to overflow — flag before the inf appears.
ABSMAX_OVERFLOW_THRESHOLD = 1e30


def health_rows(g, p2, c1n, a1n, c2n, a2n, bits_m: int, bits_r: int):
    """Per-block health counts ``(n_blocks, N_HEALTH)`` f32, HEALTH_SLOTS
    order, from one fused update's inputs/outputs: the raw (unscaled)
    grad blocks ``g``, the updated master blocks ``p2``, and the NEW
    *unpacked* codes / absmax of each state slot (pre ``pack_codes`` —
    exactly what the kernel holds in VMEM after ``block_requantize``).
    Pure jnp: runs inside the Pallas kernel tile-by-tile and at the XLA
    level post-hoc (jnp oracle / muon entry) unchanged, so sentinel
    parity across impls holds by construction.  Absmax vectors whose
    length differs from ``n_blocks`` (the tensor-wise ablation's
    per-tensor absmax) fold their counts into row 0."""
    nb = p2.shape[0]
    zero = jnp.zeros((nb,), jnp.float32)

    def nf2(x):                                   # (nb, B) -> (nb,)
        return jnp.sum((~jnp.isfinite(x)).astype(jnp.float32), axis=1)

    def amax_slots(a):
        if a is None:
            return zero, zero
        a = jnp.asarray(a, jnp.float32).reshape(-1)
        nfin = (~jnp.isfinite(a)).astype(jnp.float32)
        over = jnp.where(jnp.isfinite(a) &
                         (a > ABSMAX_OVERFLOW_THRESHOLD), 1.0, 0.0)
        if a.shape[0] == nb:
            return nfin, over
        return (zero.at[0].add(jnp.sum(nfin)),
                zero.at[0].add(jnp.sum(over)))

    def edge(c, bits):
        if c is None:
            return zero
        hit = (c == 0) | (c == (1 << bits) - 1)
        return jnp.sum(hit.astype(jnp.float32), axis=1)

    nf_a1, ov_a1 = amax_slots(a1n)
    nf_a2, ov_a2 = amax_slots(a2n)
    return jnp.stack([
        nf2(g.astype(jnp.float32)), nf2(p2.astype(jnp.float32)),
        nf_a1, nf_a2, edge(c1n, bits_m), edge(c2n, bits_r),
        ov_a1, ov_a2], axis=1)


# --------------------------------------------------------------- update math
def adam_moments(g, m, r, s):
    """Shared first/second moment EMA for the adam family (incl. lamb)."""
    m2 = s["beta1"] * m + (1.0 - s["beta1"]) * g
    r2 = s["beta2"] * r + (1.0 - s["beta2"]) * g * g
    return m2, r2


def update_scalars(*, lr, beta1, beta2, eps, weight_decay, step,
                   tensor_scale=None) -> dict:
    """The ``s`` dict of ``update_math``, with adam's bias corrections
    ``c1 = 1 - beta1^step`` and ``c2 = 1 - beta2^step``.  They are computed
    here, by XLA, for every path — Mosaic lowers no scalar ``pow``; the
    kernels read them from ``scalars_vector`` — so all paths use the same
    c1/c2.  Values keep the types they are given."""
    return dict(lr=lr, beta1=beta1, beta2=beta2, eps=eps,
                weight_decay=weight_decay, step=step,
                tensor_scale=(jnp.float32(1.0) if tensor_scale is None
                              else tensor_scale),
                c1=1.0 - jnp.power(beta1, step),
                c2=1.0 - jnp.power(beta2, step))


def adam_base_update(g, p, m, r, s):
    """Bias-corrected adam step direction incl. decoupled weight decay —
    the pre-trust-ratio 'u' of LAMB. Returns (m2, r2, u)."""
    m2, r2 = adam_moments(g, m, r, s)
    u = ((m2 / s["c1"]) / (jnp.sqrt(r2 / s["c2"]) + s["eps"])
         + s["weight_decay"] * p)
    return m2, r2, u


def update_math(spec: AlgoSpec, g, p, m, r, s):
    """One 32-bit optimizer update on (already gnorm-scaled) g.

    ``s`` is a dict of scalars: lr, beta1, beta2, eps, weight_decay, step,
    tensor_scale (the finalized LAMB trust ratio / LARS local lr; 1.0 for
    block-local algorithms).  Returns (m2, r2, p2) with r2 = None for
    one-state algorithms.  Pure jnp: runs inside the Pallas kernel and in
    the jnp reference unchanged — parity by construction.
    """
    algo = spec.name
    if algo in ("adam", "adamw"):
        m2, r2, u = adam_base_update(g, p, m, r, s)
        return m2, r2, p - s["lr"] * u
    if algo == "lamb":
        m2, r2, u = adam_base_update(g, p, m, r, s)
        return m2, r2, p - s["lr"] * s["tensor_scale"] * u
    if algo == "momentum":
        m2 = s["beta1"] * m + (g + s["weight_decay"] * p)
        return m2, None, p - s["lr"] * m2
    if algo == "lars":
        m2 = s["beta1"] * m + s["tensor_scale"] * (g + s["weight_decay"] * p)
        return m2, None, p - s["lr"] * m2
    if algo == "adagrad":
        m2 = m + g * g
        u = g / (jnp.sqrt(m2) + s["eps"]) + s["weight_decay"] * p
        return m2, None, p - s["lr"] * u
    raise ValueError(algo)


def tensor_scale_from_norms(spec: AlgoSpec, pn2, gn2, un2, *,
                            weight_decay, trust_coeff):
    """Finalize the norm-prologue partials into the main kernel's scalar.

    lamb: trust ratio ||p|| / ||u||; lars: local lr
    trust_coeff*||p|| / (||g|| + wd*||p||).  Identical guards to the
    long-standing 32-bit engine math."""
    pn = jnp.sqrt(pn2)
    if spec.norm_kind == "lamb":
        un = jnp.sqrt(un2)
        return jnp.where((pn > 0) & (un > 0),
                         pn / jnp.where(un > 0, un, 1.0), 1.0)
    if spec.norm_kind == "lars":
        gn = jnp.sqrt(gn2)
        denom = gn + weight_decay * pn + 1e-12
        return jnp.where(pn > 0, trust_coeff * pn / denom, 1.0)
    return jnp.float32(1.0)


def segment_scale_vector(segments, total: int, scale_fn):
    """Assemble a per-block (or per-element) tensor_scale vector from
    per-segment scalars: ``scale_fn(i, off, n)`` returns segment i's scalar
    scale; positions past the last segment (rows padding) get 1.0.  The
    single shared assembly point for the pooled dispatch's per-tensor trust
    ratios — the Pallas finalization, the jnp oracle and the fp32 pool all
    call it, so the pooled/per-leaf bit-exactness contract has one
    implementation to keep honest.  Segments must tile a contiguous
    prefix of ``total``."""
    pieces, cursor = [], 0
    for i, (off, n) in enumerate(segments):
        assert off == cursor, (segments, "segments must be contiguous")
        pieces.append(jnp.broadcast_to(scale_fn(i, off, n), (n,)))
        cursor += n
    if cursor < total:
        pieces.append(jnp.ones((total - cursor,), jnp.float32))
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)


def tensor_scale_for(spec: AlgoSpec, g, p, m, r, s, trust_coeff):
    """Whole-tensor norm prologue + finalization for single-tensor callers
    (the jnp oracle and the Full32 engine path).  The Pallas path computes
    the same sums as per-grid-row partials instead."""
    if not spec.needs_norms:
        return jnp.float32(1.0)
    pn2 = jnp.sum(p * p)
    gn2 = jnp.sum(g * g)
    un2 = jnp.zeros((), jnp.float32)
    if spec.norm_kind == "lamb":
        _, _, u = adam_base_update(g, p, m, r, s)
        un2 = jnp.sum(u * u)
    return tensor_scale_from_norms(spec, pn2, gn2, un2,
                                   weight_decay=s["weight_decay"],
                                   trust_coeff=trust_coeff)


def scalars_vector(lr, beta1, beta2, eps, weight_decay, step, gnorm_scale,
                   trust_coeff) -> jax.Array:
    """The (N_SCALARS,) f32 kernel scalar vector in SCALAR_SLOTS order,
    bias corrections included."""
    vals = [jnp.asarray(x, jnp.float32)
            for x in (lr, beta1, beta2, eps, weight_decay, step,
                      gnorm_scale, trust_coeff)]
    s = update_scalars(lr=vals[0], beta1=vals[1], beta2=vals[2],
                       eps=vals[3], weight_decay=vals[4], step=vals[5])
    return jnp.stack(vals + [s["c1"], s["c2"]])


def _scalars_dict(scal_ref):
    """SMEM scalar vector -> the ``s`` dict of ``update_math``."""
    return {k: scal_ref[i] for i, k in enumerate(SCALAR_SLOTS)}


# ------------------------------------------------------------ kernel builder
def _make_update_kernel(spec: AlgoSpec, rows: int, bsz: int, stochastic: bool,
                        bits_m: int, bits_r: int, sentinel: bool = False):
    """Build the main fused-update kernel for one (algo, tile, mode, bits).

    ``sentinel`` appends one trailing ``(rows, N_HEALTH)`` output of
    per-block health counts (``health_rows``) — computed on values the
    update already holds in VMEM, so the only extra HBM traffic is the
    (n_blocks, 8) f32 store itself."""
    two = spec.n_states == 2

    def kernel(*refs):
        it = iter(refs)
        scal_ref = next(it)
        seed_ref = next(it) if stochastic else None
        boff_ref = next(it) if stochastic else None
        ts_ref = next(it) if spec.needs_norms else None
        qm1_ref, b1_ref = next(it), next(it)
        qm2_ref, b2_ref = (next(it), next(it)) if two else (None, None)
        p_ref, g_ref, c1_ref, a1_ref = next(it), next(it), next(it), next(it)
        c2_ref, a2_ref = (next(it), next(it)) if two else (None, None)
        p_out, c1_out, a1_out = next(it), next(it), next(it)
        c2_out, a2_out = (next(it), next(it)) if two else (None, None)
        h_out = next(it) if sentinel else None

        s = _scalars_dict(scal_ref)
        if spec.needs_norms:
            # Per-block trust ratio / local lr from the norm prologue;
            # constant within a segment, broadcast over the block dim.
            s["tensor_scale"] = ts_ref[...]
        g = g_ref[...].astype(jnp.float32) * s["gnorm_scale"]
        p = p_ref[...].astype(jnp.float32)

        # ---- unpack sub-byte codes + dequantize (lane-table codebook) ----
        m = common.decode(unpack_codes(c1_ref[...], bits_m),
                          qm1_ref, 1 << bits_m) * a1_ref[...]
        r = (common.decode(unpack_codes(c2_ref[...], bits_r),
                           qm2_ref, 1 << bits_r) * a2_ref[...]
             if two else None)

        # ---- 32-bit update math in registers ----
        m2, r2, p2 = update_math(spec, g, p, m, r, s)
        p_out[...] = p2.astype(p_out.dtype)

        # ---- requantize (per-block absmax is a row reduction in VMEM) ----
        u1 = u2 = None
        if stochastic:
            # Per-block seed + leaf-local block offset (pooled dispatch):
            # element index is offset*B + col inside the block's own leaf,
            # so pooled and per-leaf rounding draw identical uniforms.
            seed = seed_ref[...].astype(jnp.uint32)          # (rows, 1)
            off = boff_ref[...].astype(jnp.uint32)           # (rows, 1)
            col = jax.lax.broadcasted_iota(jnp.uint32, (rows, bsz), 1)
            idx = off * jnp.uint32(bsz) + col
            u1 = common.hash_uniform(idx, seed + jnp.uint32(common.STATE1_SEED_SALT))
            if two:
                u2 = common.hash_uniform(idx, seed + jnp.uint32(common.STATE2_SEED_SALT))
        c1n, a1n = common.block_requantize(m2, b1_ref, qm1_ref,
                                           random_u=u1,
                                           max_code=(1 << bits_m) - 1)
        c1_out[...] = pack_codes(c1n, bits_m)
        a1_out[...] = a1n
        c2n = a2n = None
        if two:
            c2n, a2n = common.block_requantize(r2, b2_ref, qm2_ref,
                                               random_u=u2,
                                               max_code=(1 << bits_r) - 1)
            c2_out[...] = pack_codes(c2n, bits_r)
            a2_out[...] = a2n
        if sentinel:
            # Health counts on the RAW grad tile (pre gnorm_scale: inf*0
            # would mask a nonfinite grad) and the values already live in
            # VMEM — no second pass over HBM.
            h_out[...] = health_rows(g_ref[...], p2, c1n, a1n, c2n, a2n,
                                     bits_m, bits_r)

    return kernel


def _make_norm_kernel(spec: AlgoSpec, rows: int, bsz: int,
                      bits_m: int, bits_r: int):
    """Norm prologue: per-**block** partial squared norms, one
    (rows, N_PARTIALS) tile of rows [||p||^2, ||g||^2, ||u||^2, 0...] per grid step.  Block
    granularity (not grid-row granularity) is what lets the XLA side
    finalize the partials per *segment* under the pooled dispatch, where a
    leaf boundary need not be tile-aligned.  lars only needs p and g; lamb
    re-derives the pre-trust update u from the dequantized states."""

    def kernel(*refs):
        it = iter(refs)
        scal_ref = next(it)
        if spec.norm_kind == "lamb":
            qm1_ref, qm2_ref = next(it), next(it)
            p_ref, g_ref = next(it), next(it)
            c1_ref, a1_ref, c2_ref, a2_ref = (next(it), next(it),
                                              next(it), next(it))
        else:
            p_ref, g_ref = next(it), next(it)
        out_ref = next(it)

        s = _scalars_dict(scal_ref)
        g = g_ref[...].astype(jnp.float32) * s["gnorm_scale"]
        p = p_ref[...].astype(jnp.float32)
        pn2 = jnp.sum(p * p, axis=1)                      # (rows,)
        gn2 = jnp.sum(g * g, axis=1)
        un2 = jnp.zeros((rows,), jnp.float32)
        if spec.norm_kind == "lamb":
            m = common.decode(unpack_codes(c1_ref[...], bits_m),
                              qm1_ref, 1 << bits_m) * a1_ref[...]
            r = common.decode(unpack_codes(c2_ref[...], bits_r),
                              qm2_ref, 1 << bits_r) * a2_ref[...]
            _, _, u = adam_base_update(g, p, m, r, s)
            un2 = jnp.sum(u * u, axis=1)
        zero = jnp.zeros((rows,), jnp.float32)
        out_ref[...] = jnp.stack(
            [pn2, gn2, un2, zero, zero, zero, zero, zero], axis=1)

    return kernel


def _norm_partials_pallas(spec: AlgoSpec, p, g, codes_m, absmax_m, codes_r,
                          absmax_r, qm1, qm2, scalars, *, rows: int,
                          bits_m: int, bits_r: int, interpret: bool):
    """Run the norm prologue over the whole input: per-block partial
    squared norms, (n_blocks, N_PARTIALS) f32.  Shared by the fused update
    and the standalone segment-scale pass of the partitioned dispatch
    (DESIGN.md §12) — one implementation, so both produce bit-identical
    partials."""
    n_blocks, bsz = p.shape
    w1 = packed_width(bsz, bits_m)
    row_spec = pl.BlockSpec((rows, bsz), lambda i: (i, 0))
    code1_spec = pl.BlockSpec((rows, w1), lambda i: (i, 0))
    one_spec = pl.BlockSpec((rows, 1), lambda i: (i, 0))

    norm_kernel = _make_norm_kernel(spec, rows, bsz, bits_m, bits_r)
    in_specs = [common.smem_spec()]
    args = [scalars]
    if spec.norm_kind == "lamb":
        in_specs += [common.table_spec(), common.table_spec()]
        args += [qm1, qm2]
    in_specs += [row_spec, row_spec]
    args += [p, g]
    if spec.norm_kind == "lamb":
        w2 = packed_width(bsz, bits_r)
        code2_spec = pl.BlockSpec((rows, w2), lambda i: (i, 0))
        in_specs += [code1_spec, one_spec, code2_spec, one_spec]
        args += [codes_m, absmax_m[:, None], codes_r, absmax_r[:, None]]
    return pl.pallas_call(
        norm_kernel,
        grid=(n_blocks // rows,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rows, N_PARTIALS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks, N_PARTIALS), jnp.float32),
        interpret=interpret,
    )(*args)


def segment_scales_from_partials(spec: AlgoSpec, partials, segments,
                                 n_blocks: int, weight_decay, trust_coeff):
    """Finalize per-block norm partials into the per-block tensor_scale
    vector: a (nb_s,) sum per segment — identical in shape (hence in f32
    reduction order) to the per-leaf dispatch, the pooled/per-leaf AND
    partitioned/unpartitioned trust-ratio bit-exactness contract."""
    def seg_scale(i, off, nb):
        sums = jnp.sum(partials[off:off + nb], axis=0)
        return tensor_scale_from_norms(
            spec, sums[0], sums[1], sums[2],
            weight_decay=weight_decay, trust_coeff=trust_coeff)

    return segment_scale_vector(segments, n_blocks, seg_scale)


@functools.partial(jax.jit, static_argnames=("algo", "rows", "stochastic",
                                             "interpret", "bits_m", "bits_r",
                                             "segments"))
def segment_scales_pallas(
    p, g, codes_m, absmax_m, codes_r, absmax_r, qmap_m, qmap_r, scalars,
    *, algo: str, interpret: bool, rows: int = common.DEFAULT_ROWS,
    stochastic: bool = False, bits_m: int = 8, bits_r: int = 8,
    segments: tuple = (),
) -> jax.Array:
    """Standalone norm-prologue pass -> (n_blocks,) per-block tensor_scale,
    exactly the vector ``fused_update_pallas`` would derive internally.
    The partitioned dispatch (DESIGN.md §12) runs this once over the whole
    arena, then feeds per-span slices to the main kernel via
    ``tensor_scale_blocks`` — segment norms are global reductions and a
    leaf may straddle owned-span boundaries."""
    del stochastic
    spec = ALGO_SPECS[algo]
    n_blocks = p.shape[0]
    assert n_blocks % rows == 0, (n_blocks, rows)
    if not segments:
        segments = ((0, n_blocks),)
    if not spec.needs_norms:
        return jnp.ones((n_blocks,), jnp.float32)
    scalars = scalars.astype(jnp.float32)
    qm1 = common.padded_qmap(qmap_m)
    qm2 = common.padded_qmap(qmap_r) if spec.norm_kind == "lamb" else None
    partials = _norm_partials_pallas(
        spec, p, g, codes_m, absmax_m, codes_r, absmax_r, qm1, qm2, scalars,
        rows=rows, bits_m=bits_m, bits_r=bits_r, interpret=interpret)
    return segment_scales_from_partials(spec, partials, segments, n_blocks,
                                        scalars[4], scalars[7])


# ------------------------------------------------------------- public entry
@functools.partial(jax.jit, static_argnames=("algo", "rows", "stochastic",
                                             "interpret", "bits_m", "bits_r",
                                             "segments", "sentinel"))
def fused_update_pallas(
    p: jax.Array,                  # (n_blocks, B) f32 master params
    g: jax.Array,                  # (n_blocks, B) f32/bf16 grads
    codes_m: jax.Array,            # (n_blocks, B*bits_m/8) uint8 (packed)
    absmax_m: jax.Array,           # (n_blocks,)  f32
    codes_r: Optional[jax.Array],  # 2-state algos only
    absmax_r: Optional[jax.Array],
    qmap_m: jax.Array,             # (2^bits_m,) state-1 codebook
    qmap_r: Optional[jax.Array],   # (2^bits_r,) state-2 codebook
    scalars: jax.Array,            # (N_SCALARS,) f32, ``scalars_vector``
    block_seeds: jax.Array,        # (n_blocks,) int32 per-block rounding seeds
    block_offsets: jax.Array,      # (n_blocks,) int32 leaf-local block index
    tensor_scale_blocks: Optional[jax.Array] = None,  # (n_blocks,) f32
    *,
    algo: str,
    interpret: bool,               # no default: say whether it is the chip
    rows: int = common.DEFAULT_ROWS,
    stochastic: bool = False,
    bits_m: int = 8,
    bits_r: int = 8,
    segments: tuple = (),          # ((block_offset, n_blocks), ...) static
    sentinel: bool = False,        # emit per-block health counts (§16)
) -> FusedUpdateResult:
    """One fused k-bit update for ``algo`` in the flat block domain.

    ``n_blocks`` must be a multiple of ``rows`` (ops.fused_update pads).
    ``scalars`` layout: SCALAR_SLOTS (``scalars_vector`` builds it; slot 7
    holds trust_coeff).  ``block_seeds`` / ``block_offsets`` give
    every block its stochastic-rounding seed and its block index *within
    its own leaf* — a constant seed plus ``arange`` offsets reproduce the
    single-tensor behaviour; the pooled dispatch (DESIGN.md §10) passes one
    seed per pooled leaf so pooled and per-leaf rounding are bit-identical.
    ``segments`` lists the contiguous per-tensor block ranges the lamb/lars
    norm prologue is finalized over (empty = one segment spanning the
    input); blocks outside every segment get tensor_scale 1.0.
    ``tensor_scale_blocks`` short-circuits the norm prologue with an
    externally computed per-block vector — the partitioned dispatch
    (DESIGN.md §12) computes it globally (``segment_scales_pallas``) and
    feeds each owned span its slice, since a segment may straddle span
    boundaries.  Sub-byte state slots (``bits_m``/``bits_r`` < 8) stream
    bit-packed uint8 words and unpack/re-pack inside the kernel
    (DESIGN.md §9).
    """
    spec = ALGO_SPECS[algo]
    two = spec.n_states == 2
    n_blocks, bsz = p.shape
    assert n_blocks % rows == 0, (n_blocks, rows)
    w1 = packed_width(bsz, bits_m)
    assert codes_m.shape == (n_blocks, w1), (codes_m.shape, n_blocks, w1)
    if two:
        w2 = packed_width(bsz, bits_r)
        assert codes_r.shape == (n_blocks, w2), (codes_r.shape, n_blocks, w2)
    if not segments:
        segments = ((0, n_blocks),)
    grid = (n_blocks // rows,)

    row_spec = pl.BlockSpec((rows, bsz), lambda i: (i, 0))
    code1_spec = pl.BlockSpec((rows, w1), lambda i: (i, 0))
    code2_spec = pl.BlockSpec((rows, w2), lambda i: (i, 0)) if two else None
    one_spec = pl.BlockSpec((rows, 1), lambda i: (i, 0))
    table_spec = common.table_spec()

    qm1, b1 = common.padded_qmap(qmap_m), common.padded_bounds(qmap_m)
    if two:
        qm2, b2 = common.padded_qmap(qmap_r), common.padded_bounds(qmap_r)

    scalars = scalars.astype(jnp.float32)
    tscale_blocks = None
    if spec.needs_norms:
        if tensor_scale_blocks is not None:
            # Externally finalized scales (partitioned dispatch): the
            # caller ran the prologue globally; this span consumes its
            # slice directly.
            tscale_blocks = tensor_scale_blocks.astype(jnp.float32)[:, None]
        else:
            partials = _norm_partials_pallas(
                spec, p, g, codes_m, absmax_m, codes_r, absmax_r, qm1,
                qm2 if spec.norm_kind == "lamb" else None, scalars,
                rows=rows, bits_m=bits_m, bits_r=bits_r,
                interpret=interpret)
            tscale_blocks = segment_scales_from_partials(
                spec, partials, segments, n_blocks, scalars[4],
                scalars[7])[:, None]
    scalars = scalars.at[7].set(1.0)

    kernel = _make_update_kernel(spec, rows, bsz, stochastic, bits_m, bits_r,
                                 sentinel)
    in_specs = [common.smem_spec()]
    args = [scalars]
    if stochastic:
        in_specs += [one_spec, one_spec]
        args += [block_seeds.astype(jnp.int32)[:, None],
                 block_offsets.astype(jnp.int32)[:, None]]
    if spec.needs_norms:
        in_specs += [one_spec]
        args += [tscale_blocks]
    in_specs += [table_spec, table_spec]
    args += [qm1, b1]
    if two:
        in_specs += [table_spec, table_spec]
        args += [qm2, b2]
    in_specs += [row_spec, row_spec, code1_spec, one_spec]
    args += [p, g, codes_m, absmax_m[:, None]]
    if two:
        in_specs += [code2_spec, one_spec]
        args += [codes_r, absmax_r[:, None]]

    out_specs = [row_spec, code1_spec, one_spec]
    out_shape = [
        jax.ShapeDtypeStruct((n_blocks, bsz), jnp.float32),
        jax.ShapeDtypeStruct((n_blocks, w1), jnp.uint8),
        jax.ShapeDtypeStruct((n_blocks, 1), jnp.float32),
    ]
    if two:
        out_specs += [code2_spec, one_spec]
        out_shape += [
            jax.ShapeDtypeStruct((n_blocks, w2), jnp.uint8),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.float32),
        ]
    if sentinel:
        out_specs += [pl.BlockSpec((rows, N_HEALTH), lambda i: (i, 0))]
        out_shape += [jax.ShapeDtypeStruct((n_blocks, N_HEALTH),
                                           jnp.float32)]

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*args)
    health = outs[-1] if sentinel else None
    if two:
        p2, c1, a1, c2, a2 = outs[:5]
        return FusedUpdateResult(p2, c1, a1[:, 0], c2, a2[:, 0], health)
    p2, c1, a1 = outs[:3]
    return FusedUpdateResult(p2, c1, a1[:, 0], None, None, health)
