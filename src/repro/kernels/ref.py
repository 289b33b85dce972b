"""Pure-jnp oracles for every Pallas kernel in this package.

These are the numerical source of truth the kernels are tested against
(tests/test_kernels.py sweeps shapes/dtypes and asserts allclose).
All functions operate in the flat block domain: state tensors are
``(n_blocks, B)``, absmax is ``(n_blocks,)``.

``fused_update_ref`` is the single parameterized reference for the fused
optimizer update: it shares the 32-bit update math and norm finalization
with ``fused_update.py`` (parity by construction) but keeps independent
quantization mechanics (searchsorted + gather instead of the kernels'
lane-gather binary search and lookup).  It also implements the ablation modes
the Pallas path does not serve: tensor-wise (single absmax) quantization.
It is registered in ``ops.py`` as the ``impl="jnp"`` entry for every
algorithm — the only surviving form of the old multi-pass jnp fallback.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels import fused_update as fu
from repro.kernels import newton_schulz as ns


def _bounds(codebook: jax.Array) -> jax.Array:
    return (codebook[1:] + codebook[:-1]) * 0.5


def quantize_ref(x: jax.Array, codebook: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(n_blocks, B) f32 -> (codes uint8, absmax f32)."""
    x = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.where(absmax > 0, absmax, 1.0)
    xn = x / scale[:, None]
    codes = jnp.searchsorted(_bounds(codebook), xn, side="right")
    return codes.astype(jnp.uint8), absmax


def dequantize_ref(codes: jax.Array, absmax: jax.Array, codebook: jax.Array,
                   dtype=jnp.float32) -> jax.Array:
    return (codebook[codes.astype(jnp.int32)] * absmax[:, None]).astype(dtype)


def _requantize(x: jax.Array, codebook: jax.Array, *, blockwise: bool,
                random_u: Optional[jax.Array]) -> tuple[jax.Array, jax.Array]:
    """Requantize one state tensor: block-wise or tensor-wise absmax,
    optionally with stochastic rounding (same uniforms as the kernel)."""
    if blockwise:
        absmax = jnp.max(jnp.abs(x), axis=-1)
    else:
        # tensor-wise ablation: a single absmax for the whole tensor
        absmax = jnp.full((x.shape[0],), jnp.max(jnp.abs(x)), jnp.float32)
    scale = jnp.where(absmax > 0, absmax, 1.0)
    xn = x / scale[:, None]
    codes = jnp.searchsorted(_bounds(codebook), xn, side="right").astype(jnp.int32)
    if random_u is not None:
        q_near = codebook[codes]
        direction = jnp.where(xn > q_near, 1, -1)
        # k-bit codebooks have 2^bits levels; clip at the last real one.
        other = jnp.clip(codes + direction, 0, codebook.shape[0] - 1)
        q_other = codebook[other]
        codes = common.stochastic_codes(xn, codes, q_near, q_other, other,
                                        random_u)
    return codes.astype(jnp.uint8), absmax


def _segment_scales(spec, g, p, m, r, s, trust_coeff, segments):
    """Per-block tensor_scale vector from per-segment trust ratios, on
    global 2-D slices — the jnp analogue of the kernels' prologue+finalize
    (shared by ``fused_update_ref`` and ``segment_scales_ref`` so the
    partitioned dispatch consumes bit-identical scales)."""
    two = spec.n_states == 2

    def seg_scale(i, off, nb):
        sl = slice(off, off + nb)
        return fu.tensor_scale_for(spec, g[sl], p[sl], m[sl],
                                   r[sl] if two else None, s, trust_coeff)

    return fu.segment_scale_vector(segments, p.shape[0], seg_scale)


def segment_scales_ref(
    p, g, codes_m, absmax_m, codes_r, absmax_r, qmap_m, qmap_r, *,
    algo: str, lr, beta1=0.9, beta2=0.999, eps=1e-8, weight_decay=0.0,
    step=1.0, trust_coeff=0.001, gnorm_scale=1.0, segments=None,
) -> jax.Array:
    """Standalone (n_blocks,) per-block tensor_scale pass, exactly the
    vector ``fused_update_ref`` derives internally — run once over the
    whole arena by the partitioned dispatch (DESIGN.md §12), which then
    slices it per owned span (a segment may straddle span boundaries)."""
    spec = fu.ALGO_SPECS[algo]
    n_blocks = p.shape[0]
    if not spec.needs_norms:
        return jnp.ones((n_blocks,), jnp.float32)
    two = spec.n_states == 2
    p = p.astype(jnp.float32)
    g = g.astype(jnp.float32) * jnp.asarray(gnorm_scale, jnp.float32)
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    s = fu.update_scalars(lr=f32(lr), beta1=f32(beta1), beta2=f32(beta2),
                          eps=f32(eps), weight_decay=f32(weight_decay),
                          step=f32(step))
    m = dequantize_ref(codes_m, absmax_m, qmap_m)
    r = dequantize_ref(codes_r, absmax_r, qmap_r) if two else None
    segments = tuple(segments) if segments else ((0, n_blocks),)
    return _segment_scales(spec, g, p, m, r, s,
                           jnp.asarray(trust_coeff, jnp.float32), segments)


def fused_update_ref(
    p: jax.Array,                  # (n_blocks, B) f32 master params
    g: jax.Array,                  # (n_blocks, B) grads
    codes_m: jax.Array,            # (n_blocks, B) uint8
    absmax_m: jax.Array,           # (n_blocks,)   f32
    codes_r: Optional[jax.Array],  # 2-state algos only
    absmax_r: Optional[jax.Array],
    qmap_m: jax.Array,             # (2^bits,) state-1 codebook
    qmap_r: Optional[jax.Array],   # (2^bits,) state-2 codebook
    *,
    algo: str,
    lr, beta1=0.9, beta2=0.999, eps=1e-8, weight_decay=0.0, step=1.0,
    trust_coeff=0.001, gnorm_scale=1.0,
    blockwise: bool = True,
    stochastic: bool = False,
    seed=0,
    block_seeds=None,
    block_offsets=None,
    segments=None,
    tensor_scale_blocks=None,
) -> fu.FusedUpdateResult:
    """The paper's §2 procedure (dequantize -> 32-bit update -> requantize)
    for any of the six algorithms, as straight-line XLA ops.

    ``block_seeds`` / ``block_offsets`` / ``segments`` carry the pooled
    dispatch's per-leaf identity (see ``ops.fused_update``); None keeps the
    single-tensor behaviour.  Per-segment trust ratios are computed on
    static slices so each segment's reduction has exactly the shape the
    per-leaf call would use — pooled and per-leaf results stay bit-exact.
    ``tensor_scale_blocks`` overrides the trust-ratio computation with an
    externally finalized per-block vector (the partitioned dispatch,
    DESIGN.md §12 — segments may straddle owned-span boundaries, so scales
    are computed globally via ``segment_scales_ref`` and sliced per span).
    """
    spec = fu.ALGO_SPECS[algo]
    two = spec.n_states == 2
    p = p.astype(jnp.float32)
    g = g.astype(jnp.float32) * jnp.asarray(gnorm_scale, jnp.float32)
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    s = fu.update_scalars(lr=f32(lr), beta1=f32(beta1), beta2=f32(beta2),
                          eps=f32(eps), weight_decay=f32(weight_decay),
                          step=f32(step))

    m = dequantize_ref(codes_m, absmax_m, qmap_m)
    r = dequantize_ref(codes_r, absmax_r, qmap_r) if two else None

    tc = jnp.asarray(trust_coeff, jnp.float32)
    if tensor_scale_blocks is not None:
        s["tensor_scale"] = tensor_scale_blocks.astype(jnp.float32)[:, None]
    elif spec.needs_norms and segments:
        s["tensor_scale"] = _segment_scales(spec, g, p, m, r, s, tc,
                                            segments)[:, None]
    else:
        s["tensor_scale"] = fu.tensor_scale_for(spec, g, p, m, r, s, tc)

    m2, r2, p2 = fu.update_math(spec, g, p, m, r, s)

    u1 = u2 = None
    if stochastic:
        if block_seeds is None:
            seed = jnp.asarray(seed, jnp.int32).astype(jnp.uint32)
            idx = common.element_indices(*codes_m.shape, 0)
        else:
            nb_, bsz = codes_m.shape
            offs = (jnp.arange(nb_, dtype=jnp.uint32) if block_offsets is None
                    else block_offsets.astype(jnp.uint32))
            col = jax.lax.broadcasted_iota(jnp.uint32, (nb_, bsz), 1)
            idx = offs[:, None] * jnp.uint32(bsz) + col
            seed = block_seeds.astype(jnp.uint32)[:, None]
        u1 = common.hash_uniform(idx, seed + jnp.uint32(common.STATE1_SEED_SALT))
        if two:
            u2 = common.hash_uniform(idx, seed + jnp.uint32(common.STATE2_SEED_SALT))
    cm, am = _requantize(m2, qmap_m, blockwise=blockwise, random_u=u1)
    if two:
        cr, ar = _requantize(r2, qmap_r, blockwise=blockwise, random_u=u2)
        return fu.FusedUpdateResult(p2, cm, am, cr, ar)
    return fu.FusedUpdateResult(p2, cm, am, None, None)


def newton_schulz_ref(x: jax.Array, *, steps: int = ns.DEFAULT_NS_STEPS,
                      eps: float = 1e-7) -> jax.Array:
    """≈ orth(x) — the pure-jnp Newton–Schulz oracle (DESIGN.md §11).

    The quintic iteration X ← aX + b(XX^T)X + c(XX^T)²X on the Frobenius-
    normalized input, min-dim-first via the transpose.  Numerically this is
    the same tile-replaying path the Pallas kernels mirror
    (``newton_schulz.newton_schulz(impl="jnp")``), so kernel parity tests
    have a single source of truth to compare against.
    """
    return ns.newton_schulz(x, steps=steps, impl="jnp", eps=eps)


def muon_update_ref(p, g, codes_m, absmax_m, qmap_m, *, lr, beta1=0.95,
                    weight_decay=0.0, gnorm_scale=1.0, stochastic=False,
                    seed=0,
                    ns_steps: int = ns.DEFAULT_NS_STEPS) -> fu.FusedUpdateResult:
    """Muon leaf update oracle: dequantize the block-domain momentum,
    nesterov-EMA it with the matrix-shaped gradient, Newton–Schulz-
    orthogonalize, step the param, requantize (DESIGN.md §11).  This is
    the ``("muon", "jnp")`` registry entry's math, re-exported here next
    to the other oracles; parity with "interpret"/"pallas" holds because
    only the NS matmul chain is impl-routed.
    """
    from repro.kernels import ops as kops
    return kops.fused_update(
        "muon", p, g, codes_m, absmax_m, qmap_m=qmap_m, lr=lr, beta1=beta1,
        weight_decay=weight_decay, gnorm_scale=gnorm_scale,
        stochastic=stochastic, seed=seed, ns_steps=ns_steps, impl="jnp")
