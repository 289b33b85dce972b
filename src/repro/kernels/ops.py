"""Public jit'd wrappers around the Pallas kernels, plus the fused-update
registry.

``impl`` selects the execution path:
  * "pallas"   — pl.pallas_call, compiled for TPU (interpret=False).
  * "interpret"— pl.pallas_call with interpret=True (CPU validation path).
  * "jnp"      — the pure-jnp oracle from ref.py (XLA codegen; used inside the
                 distributed train step so the 512-device dry-run doesn't have
                 to lower the interpreter graph — see DESIGN.md §3).

``default_impl()`` picks "pallas" on TPU and "jnp" elsewhere.

The fused optimizer update is a **registry** keyed by ``(algo, impl)`` with
one public entry point, :func:`fused_update` — the analogue of bitsandbytes'
single ``optimizer_update_8bit_blockwise`` routing every optimizer through
one kernel family.  All six algorithms (adam/adamw/momentum/lamb/lars/
adagrad) and all ablation modes (stochastic rounding, tensor-wise
quantization) go through it; the old per-algorithm wrappers and the
multi-pass jnp fallback are gone.  Register new backends with
:func:`register`.

Sub-byte state bitwidths (4/5/6-bit, DESIGN.md §9) ride through the same
entry point: callers pass :class:`~repro.core.lowbit.PackedCodes`
containers instead of plain uint8 code arrays.  ``fused_update`` unwraps
them, threads the static per-slot bitwidths to the backend (the Pallas
kernels unpack/re-pack in VMEM; the jnp oracle unpacks at the XLA level),
and re-wraps the results, so the optimizer engine is bitwidth-agnostic.

Matrix-class algorithms (``muon``, DESIGN.md §11) register under the same
keys: their entries take ``p``/``g`` in the leaf's 2-D param shape and run
the Newton–Schulz matmul chain (``kernels/newton_schulz.py``) between
dequantize and requantize.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.analysis import contracts as _contracts
from repro.analysis import mutations as _mutations
from repro.core.lowbit import (PackedCodes, pack_codes, unpack_codes,
                               unwrap_codes)
from repro.telemetry import tracing as _tracing
from repro.kernels import common, ref
from repro.kernels import fused_update as _fu
from repro.kernels import newton_schulz as _ns
from repro.kernels.blockwise_dequant import dequantize_blockwise as _dequant_pallas
from repro.kernels.blockwise_quant import quantize_blockwise as _quant_pallas

DEFAULT_ROWS = common.DEFAULT_ROWS
ALGOS = tuple(_fu.ALGO_SPECS)
IMPLS = ("pallas", "interpret", "jnp")


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def _pad_rows(arrs, n_blocks: int, rows: int):
    """Pad the block dim of each (n_blocks, ...) array to a multiple of rows."""
    target = -(-n_blocks // rows) * rows
    if target == n_blocks:
        return arrs, n_blocks
    pad = target - n_blocks
    out = []
    for a in arrs:
        cfg = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        out.append(jnp.pad(a, cfg))
    return out, target


def quantize_blockwise(x, codebook, *, impl: str | None = None,
                       rows: int = DEFAULT_ROWS):
    with _tracing.annotate("quantize"):
        impl = impl or default_impl()
        if impl == "jnp":
            return ref.quantize_ref(x, codebook)
        nb = x.shape[0]
        (x,), _ = _pad_rows([x], nb, rows)
        codes, absmax = _quant_pallas(x, codebook, rows=rows,
                                      interpret=(impl == "interpret"))
        return codes[:nb], absmax[:nb]


def dequantize_blockwise(codes, absmax, codebook, *, impl: str | None = None,
                         rows: int = DEFAULT_ROWS, dtype=jnp.float32):
    with _tracing.annotate("dequantize"):
        impl = impl or default_impl()
        if impl == "jnp":
            return ref.dequantize_ref(codes, absmax, codebook, dtype)
        nb = codes.shape[0]
        (codes, absmax), _ = _pad_rows([codes, absmax], nb, rows)
        out = _dequant_pallas(codes, absmax, codebook, rows=rows,
                              interpret=(impl == "interpret"), dtype=dtype)
        return out[:nb]


# ----------------------------------------------------- fused-update registry
_REGISTRY: dict[tuple[str, str], Callable] = {}

# Dispatch counter: incremented once per fused_update() call.  Under jit the
# count advances at trace time, so "calls recorded while tracing one train
# step" == "fused launches baked into the compiled step" — what
# benchmarks/bench_speed.py reports as launches_per_step for the pooled
# dispatch (DESIGN.md §10).
_FUSED_UPDATE_CALLS = [0]


def reset_fused_update_count() -> None:
    _FUSED_UPDATE_CALLS[0] = 0


def fused_update_count() -> int:
    return _FUSED_UPDATE_CALLS[0]


@contextlib.contextmanager
def dispatch_count_paused():
    """Suspend the dispatch counter for shape-only traces (e.g. the
    eval_shape out-spec inference in sharding/rules.py): fused_update
    calls made inside the block do not count as launches."""
    n0 = _FUSED_UPDATE_CALLS[0]
    try:
        yield
    finally:
        _FUSED_UPDATE_CALLS[0] = n0


def register(algo: str, impl: str, fn: Callable) -> None:
    """Register a fused-update backend under ``(algo, impl)``.  ``fn`` takes
    (p, g, codes_m, absmax_m, codes_r, absmax_r, qmap_m, qmap_r, **hyper)
    and returns a :class:`~repro.kernels.fused_update.FusedUpdateResult`."""
    _REGISTRY[(algo, impl)] = fn


def registered(algo: str | None = None) -> list[tuple[str, str]]:
    """Registry keys, optionally filtered by algorithm."""
    return sorted(k for k in _REGISTRY if algo is None or k[0] == algo)


def _pallas_entry(algo: str, interpret: bool) -> Callable:
    def run(p, g, cm, am, cr, ar, qmap_m, qmap_r, *,
            lr, beta1, beta2, eps, weight_decay, step, trust_coeff,
            gnorm_scale, stochastic, seed, rows, bits_m=8, bits_r=8,
            block_seeds=None, block_offsets=None, segments=None,
            tensor_scale_blocks=None, sentinel=False):
        scalars = _fu.scalars_vector(lr, beta1, beta2, eps, weight_decay,
                                     step, gnorm_scale, trust_coeff)
        two = _fu.ALGO_SPECS[algo].n_states == 2
        nb = p.shape[0]
        # Single-tensor defaults: one segment, a shared seed, arange block
        # offsets — bit-identical to the historical per-leaf behaviour.
        if block_seeds is None:
            block_seeds = jnp.broadcast_to(
                jnp.asarray(seed, jnp.int32), (nb,))
        if block_offsets is None:
            block_offsets = jnp.arange(nb, dtype=jnp.int32)
        segments = tuple(segments) if segments else ((0, nb),)
        arrs = [p, g, cm, am, block_seeds, block_offsets] \
            + ([cr, ar] if two else [])
        arrs, _ = _pad_rows(arrs, nb, rows)
        p, g, cm, am, block_seeds, block_offsets = arrs[:6]
        cr, ar = (arrs[6], arrs[7]) if two else (None, None)
        if tensor_scale_blocks is not None:
            (tensor_scale_blocks,), _ = _pad_rows(
                [tensor_scale_blocks], nb, rows)
        res = _fu.fused_update_pallas(
            p, g, cm, am, cr, ar, qmap_m, qmap_r if two else None, scalars,
            block_seeds, block_offsets, tensor_scale_blocks, algo=algo,
            rows=rows, stochastic=stochastic, interpret=interpret,
            bits_m=bits_m, bits_r=bits_r, segments=segments,
            sentinel=sentinel)
        return _fu.FusedUpdateResult(
            res.p[:nb], res.codes_m[:nb], res.absmax_m[:nb],
            res.codes_r[:nb] if two else None,
            res.absmax_r[:nb] if two else None,
            res.health[:nb] if sentinel else None)
    return run


def _jnp_entry(algo: str) -> Callable:
    def run(p, g, cm, am, cr, ar, qmap_m, qmap_r, *,
            blockwise=True, rows=DEFAULT_ROWS, bits_m=8, bits_r=8, **hyper):
        del rows  # no tiling on the XLA path
        sentinel = hyper.pop("sentinel", False)
        # Sub-byte codes arrive packed; the oracle works on unpacked codes
        # and re-packs at the boundary (XLA fuses the shifts either way).
        cm = unpack_codes(cm, bits_m).astype(jnp.uint8)
        if cr is not None:
            cr = unpack_codes(cr, bits_r).astype(jnp.uint8)
        res = ref.fused_update_ref(p, g, cm, am, cr, ar, qmap_m, qmap_r,
                                   algo=algo, blockwise=blockwise, **hyper)
        health = None
        if sentinel:
            # Post-hoc on the oracle's unpacked codes — same raw-grad /
            # pre-pack operands as the in-kernel path, so the counts agree
            # by construction.
            health = _fu.health_rows(g, res.p, res.codes_m, res.absmax_m,
                                     res.codes_r, res.absmax_r,
                                     bits_m, bits_r)
        return _fu.FusedUpdateResult(
            res.p, pack_codes(res.codes_m, bits_m), res.absmax_m,
            None if res.codes_r is None else pack_codes(res.codes_r, bits_r),
            res.absmax_r, health)
    return run


def _muon_entry(impl: str) -> Callable:
    """Matrix-class (muon) fused update (DESIGN.md §11): p/g arrive in the
    leaf's 2-D param shape, the single quantized momentum state in the flat
    block domain.  dequant → momentum EMA → Newton–Schulz orthogonalization
    (kernels/newton_schulz.py, routed by ``impl``) → param update →
    blockwise requant.  Quantization mechanics ride the XLA level for every
    impl (they are element-wise and fuse there); the matmul chain is the
    kernel.  Stochastic rounding draws the same counter-hash uniforms as
    the element-wise family, so restarts and impl-parity stay bit-exact.
    """
    def run(p, g, cm, am, cr, ar, qmap_m, qmap_r, *,
            lr, beta1, weight_decay, gnorm_scale, stochastic, seed,
            bits_m=8, ns_steps=_ns.DEFAULT_NS_STEPS, blockwise=True,
            sentinel=False, **_unused):
        del cr, ar, qmap_r, _unused
        if not blockwise:
            raise NotImplementedError(
                "muon serves block-wise quantization only (the tensor-wise "
                "ablation is element-wise; DESIGN.md §11)")
        if p.ndim != 2:
            raise ValueError(
                f"muon takes the leaf in its 2-D param shape, got {p.shape} "
                f"(DESIGN.md §11)")
        shape = p.shape
        n = shape[0] * shape[1]
        nb = cm.shape[0]
        codes = unpack_codes(cm, bits_m).astype(jnp.uint8)
        bsz = codes.shape[1]
        m = ref.dequantize_ref(codes, am, qmap_m)
        m = m.reshape(-1)[:n].reshape(shape)
        g32 = g.astype(jnp.float32) * jnp.asarray(gnorm_scale, jnp.float32)
        m2, p2 = _ns.muon_math(g32, p.astype(jnp.float32), m, beta1=beta1,
                               lr=lr, weight_decay=weight_decay,
                               steps=ns_steps, impl=impl)
        blocks = jnp.pad(m2.reshape(-1), (0, nb * bsz - n)).reshape(nb, bsz)
        u1 = None
        if stochastic:
            idx = common.element_indices(nb, bsz, 0)
            u1 = common.hash_uniform(
                idx, jnp.asarray(seed, jnp.int32).astype(jnp.uint32)
                + jnp.uint32(common.STATE1_SEED_SALT))
        cm2, am2 = ref._requantize(blocks, qmap_m, blockwise=True,
                                   random_u=u1)
        health = None
        if sentinel:
            # Health on block-domain views of the raw grad and the updated
            # param (padding is finite zeros, so counts are unaffected).
            gb = jnp.pad(g.astype(jnp.float32).reshape(-1),
                         (0, nb * bsz - n)).reshape(nb, bsz)
            pb = jnp.pad(p2.astype(jnp.float32).reshape(-1),
                         (0, nb * bsz - n)).reshape(nb, bsz)
            health = _fu.health_rows(gb, pb, cm2, am2, None, None,
                                     bits_m, 8)
        return _fu.FusedUpdateResult(p2, pack_codes(cm2, bits_m), am2,
                                     None, None, health)
    return run


for _algo in ALGOS:
    if _fu.ALGO_SPECS[_algo].matrix:
        for _impl in IMPLS:
            register(_algo, _impl, _muon_entry(_impl))
        continue
    register(_algo, "pallas", _pallas_entry(_algo, interpret=False))
    register(_algo, "interpret", _pallas_entry(_algo, interpret=True))
    register(_algo, "jnp", _jnp_entry(_algo))


def fused_update(
    algo: str,
    p, g, codes_m, absmax_m, codes_r=None, absmax_r=None,
    qmap_m=None, qmap_r=None,
    *,
    lr, beta1=0.9, beta2=0.999, eps=1e-8, weight_decay=0.0, step=1.0,
    trust_coeff=0.001, gnorm_scale=1.0,
    blockwise: bool = True,
    stochastic: bool = False,
    seed=0,
    block_seeds=None,
    block_offsets=None,
    segments=None,
    tensor_scale_blocks=None,
    ns_steps: int = _ns.DEFAULT_NS_STEPS,
    impl: Optional[str] = None,
    rows: int = DEFAULT_ROWS,
    sentinel: bool = False,
) -> _fu.FusedUpdateResult:
    """One fused k-bit optimizer step in the flat block domain.

    Single entry point for every algorithm and ablation mode; dispatches on
    the ``(algo, impl)`` registry.  Tensor-wise quantization
    (``blockwise=False``) is an accuracy ablation, not a perf path, and is
    served by the "jnp" entry regardless of ``impl``.  ``codes_m`` /
    ``codes_r`` may be plain uint8 arrays (8-bit states) or
    :class:`~repro.core.lowbit.PackedCodes` (sub-byte states); results come
    back in the same container type.

    Pooled dispatch (DESIGN.md §10): when the input concatenates several
    logical tensors, pass ``block_seeds`` (per-block int32 rounding seeds —
    each leaf's seed repeated over its blocks), ``block_offsets``
    (per-block int32 index of each block *within its leaf*) and static
    ``segments`` (contiguous ``(block_offset, n_blocks)`` per-tensor
    ranges, used by the lamb/lars per-tensor norm finalization).  Left at
    None they default to the single-tensor interpretation (shared ``seed``,
    ``arange`` offsets, one segment).  ``tensor_scale_blocks`` (partitioned
    dispatch, DESIGN.md §12) bypasses the norm machinery entirely with a
    precomputed per-block trust-ratio vector — see
    :func:`segment_tensor_scales`.  Returns a
    :class:`~repro.kernels.fused_update.FusedUpdateResult` whose
    codes_r/absmax_r are None for one-state algorithms.

    ``sentinel=True`` (DESIGN.md §16) additionally fills
    ``FusedUpdateResult.health`` with per-block f32 count rows in the
    ``fused_update.HEALTH_SLOTS`` layout, computed on the values already
    in VMEM on the Pallas path and post-hoc (identical operands) on the
    jnp/muon paths; off, the field is None and the lowering is
    byte-identical to a sentinel-free build.

    Matrix-class algorithms (``muon``, DESIGN.md §11) take ``p``/``g`` in
    the leaf's 2-D *param shape* (not the flat block domain); ``codes_m``/
    ``absmax_m`` stay block-domain.  ``ns_steps`` sets the Newton–Schulz
    iteration count and is ignored by element-wise algorithms.
    """
    impl = impl or default_impl()
    if not blockwise:
        impl = "jnp"
    fn = _REGISTRY.get((algo, impl))
    if fn is None:
        raise KeyError(f"no fused_update backend for (algo={algo!r}, "
                       f"impl={impl!r}); registered: {registered()}")

    has_second = codes_r is not None
    codes_m, bits_m, ncodes_m = unwrap_codes(codes_m)
    codes_r, bits_r, ncodes_r = unwrap_codes(codes_r)
    checks = [(qmap_m, bits_m, "qmap_m")]
    if has_second:
        checks.append((qmap_r, bits_r, "qmap_r"))
    for qm, bits, nm in checks:
        if qm is not None and qm.shape[-1] != (1 << bits):
            raise ValueError(f"{nm} has {qm.shape[-1]} levels; "
                             f"{bits}-bit codes need {1 << bits}")

    hyper = dict(lr=lr, beta1=beta1, beta2=beta2, eps=eps,
                 weight_decay=weight_decay, step=step,
                 trust_coeff=trust_coeff, gnorm_scale=gnorm_scale,
                 stochastic=stochastic, seed=seed, rows=rows,
                 bits_m=bits_m, bits_r=bits_r,
                 block_seeds=block_seeds, block_offsets=block_offsets,
                 segments=None if segments is None else tuple(segments),
                 tensor_scale_blocks=tensor_scale_blocks, sentinel=sentinel)
    if _fu.ALGO_SPECS[algo].matrix:
        hyper["ns_steps"] = ns_steps
        hyper["blockwise"] = blockwise
    elif impl == "jnp":
        hyper["blockwise"] = blockwise
    if _mutations.active("promote_f64"):
        # Seeded violation for the no_dtype(f64) auditor (analysis §15):
        # promote the gradient so the whole update chain lowers in f64.
        g = g.astype(jnp.float64)
    with _tracing.annotate(f"fused_update.{algo}"):
        _FUSED_UPDATE_CALLS[0] += 1
        res = fn(p, g, codes_m, absmax_m, codes_r, absmax_r, qmap_m, qmap_r,
                 **hyper)
    if ncodes_m is not None:
        res = res._replace(codes_m=PackedCodes(res.codes_m, bits_m, ncodes_m))
    if ncodes_r is not None and res.codes_r is not None:
        res = res._replace(codes_r=PackedCodes(res.codes_r, bits_r, ncodes_r))
    return res


# ------------------------------------------------- compile contracts (§15)
# The fused-update chain is where a silent promotion or a low-precision
# accumulation would hide: every algo routes through fused_update, so the
# contracts bind to the bare update lowering per (algo, bits) matrix cell.
_contracts.register(
    "fused_update.no_f64", "update",
    lambda low, cell: _contracts.check_no_dtype(low.text, "f64"),
    doc="the update chain never promotes past f32 (§6 master-dtype policy)")
_contracts.register(
    "fused_update.accumulates_in_f32", "update",
    lambda low, cell: _contracts.check_accumulates_in(low.text, "f32"),
    doc="every matmul/additive reduction in the update (LAMB/LARS norms, "
        "NS gram chain) accumulates in f32 (§11)")


def segment_tensor_scales(
    algo: str,
    p, g, codes_m, absmax_m, codes_r=None, absmax_r=None,
    qmap_m=None, qmap_r=None,
    *,
    lr, beta1=0.9, beta2=0.999, eps=1e-8, weight_decay=0.0, step=1.0,
    trust_coeff=0.001, gnorm_scale=1.0,
    segments=None,
    impl: Optional[str] = None,
    rows: int = DEFAULT_ROWS,
) -> jax.Array:
    """Global per-block tensor_scale pass for the partitioned dispatch
    (DESIGN.md §12): the LAMB/LARS trust ratio is a whole-segment norm, and
    a segment may straddle owned-span boundaries, so the partitioned
    optimizer runs this ONCE over the full arena and hands each span its
    slice via ``fused_update(..., tensor_scale_blocks=...)``.

    Per ``impl`` this is exactly the computation ``fused_update`` performs
    internally (the Pallas norm prologue + per-segment finalize, or the jnp
    oracle's static-slice reductions), so partitioned and unpartitioned
    dispatch consume bit-identical scales.  Returns all-ones for
    block-local algorithms."""
    impl = impl or default_impl()
    spec = _fu.ALGO_SPECS[algo]
    nb = p.shape[0]
    if not spec.needs_norms:
        return jnp.ones((nb,), jnp.float32)

    codes_m, bits_m, _ = unwrap_codes(codes_m)
    codes_r, bits_r, _ = unwrap_codes(codes_r)
    segments = tuple(segments) if segments else ((0, nb),)
    hyper = dict(lr=lr, beta1=beta1, beta2=beta2, eps=eps,
                 weight_decay=weight_decay, step=step,
                 trust_coeff=trust_coeff, gnorm_scale=gnorm_scale)
    if impl == "jnp":
        cm = unpack_codes(codes_m, bits_m).astype(jnp.uint8)
        cr = (unpack_codes(codes_r, bits_r).astype(jnp.uint8)
              if codes_r is not None else None)
        return ref.segment_scales_ref(p, g, cm, absmax_m, cr, absmax_r,
                                      qmap_m, qmap_r, algo=algo,
                                      segments=segments, **hyper)
    scalars = _fu.scalars_vector(lr, beta1, beta2, eps, weight_decay, step,
                                 gnorm_scale, trust_coeff)
    two = spec.n_states == 2
    arrs = [p, g, codes_m, absmax_m] + ([codes_r, absmax_r] if two else [])
    arrs, _ = _pad_rows(arrs, nb, rows)
    p, g, codes_m, absmax_m = arrs[:4]
    codes_r, absmax_r = (arrs[4], arrs[5]) if two else (None, None)
    out = _fu.segment_scales_pallas(
        p, g, codes_m, absmax_m, codes_r, absmax_r, qmap_m,
        qmap_r if two else None, scalars, algo=algo, rows=rows,
        interpret=(impl == "interpret"), bits_m=bits_m, bits_r=bits_r,
        segments=segments)
    return out[:nb]
