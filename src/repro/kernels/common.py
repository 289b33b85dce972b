"""Shared in-kernel helpers for the 8-bit optimizer Pallas kernels.

TPU adaptation notes (DESIGN.md §3): the CUDA kernels of the paper use
per-thread binary search + shared-memory LUTs.  On TPU we use gather-free
formulations that keep every tile 2-D (Mosaic lowers no 2-D -> 1-D shape
cast and no value-level ``dynamic_slice``):

  * nearest-code search: ``code = sum_j [x >= b_j]`` over the midpoint
    boundaries — one scalar-broadcast compare + integer add per boundary
    on the VPU, looped over the codebook.
  * codebook lookup: ``where(code == j, q_j, acc)`` per codebook entry —
    an exact select chain (no MXU pass, so no reduced-precision matmul can
    round the looked-up value).

Codebooks and boundaries are padded to 256 entries (boundary 256 = +inf)
and travel as 1-D ``(256,)`` f32 vectors; the kernels stage them in SMEM
(``smem_spec``) so ``b_j`` / ``q_j`` are scalar loads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CODEBOOK_SIZE = 256
# Quantization blocks per grid step, shared by every kernel in this package
# (DESIGN.md §3: one value so the fused-update and quant/dequant kernels tile
# the flat block domain identically).  32 rows is the TPU's native sublane
# tiling of 8-bit arrays, so uint8 code tiles (packed or not) are
# tile-aligned.
DEFAULT_ROWS = 32
# Seed offsets decorrelating the two state tensors' stochastic rounding.
STATE1_SEED_SALT = 0
STATE2_SEED_SALT = 0x9E3779B9


def smem_spec() -> pl.BlockSpec:
    """Whole-array SMEM operand: codebooks, boundaries, scalar vectors."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def padded_bounds(codebook) -> jax.Array:
    """Midpoint decision boundaries padded with +inf to 256 entries, (256,).

    For an L-entry codebook (L = 2^bits ≤ 256) the L-1 real boundaries are
    followed by +inf padding, so ``encode`` can only emit codes < L — the
    k-bit maps cap their code range for free."""
    cb = jnp.asarray(codebook, dtype=jnp.float32)
    b = (cb[1:] + cb[:-1]) * 0.5
    pad = CODEBOOK_SIZE - b.shape[0]
    return jnp.concatenate([b, jnp.full((pad,), jnp.inf, jnp.float32)])


def padded_qmap(codebook) -> jax.Array:
    """Codebook zero-padded to 256 entries, (256,) f32.  Padding entries are
    unreachable: codes from ``encode``/``block_requantize`` stay below the
    real level count."""
    cb = jnp.asarray(codebook, dtype=jnp.float32)
    pad = CODEBOOK_SIZE - cb.shape[0]
    if pad:
        cb = jnp.concatenate([cb, jnp.zeros((pad,), jnp.float32)])
    return cb


def encode(x_norm: jax.Array, bounds, n_levels: int = CODEBOOK_SIZE
           ) -> jax.Array:
    """Nearest-code indices for normalized values in [-1, 1].

    x_norm: (..., N) f32; bounds: (256,) f32 boundaries (an SMEM ref inside
    a kernel, an array outside).  Returns int32 codes.
    ``sum_j [x >= b_j]`` == searchsorted(side='right').  ``n_levels``
    (2^bits for k-bit maps) bounds the sweep to the real boundaries, so
    sub-byte codebooks skip ~15/16 of the compare work.
    """
    def body(j, acc):
        return acc + (x_norm >= bounds[j]).astype(jnp.int32)
    return jax.lax.fori_loop(0, min(n_levels, CODEBOOK_SIZE) - 1, body,
                             jnp.zeros(x_norm.shape, jnp.int32))


def decode(codes: jax.Array, qmap, n_levels: int = CODEBOOK_SIZE
           ) -> jax.Array:
    """Codebook lookup as an exact select chain.

    codes: (..., N) int32 in [0, n_levels); qmap: (256,) f32 (SMEM ref
    inside a kernel, array outside).  ``n_levels`` bounds the sweep
    (codes never reach padded entries).
    """
    def body(j, acc):
        return jnp.where(codes == j, qmap[j], acc)
    return jax.lax.fori_loop(0, min(n_levels, CODEBOOK_SIZE), body,
                             jnp.zeros(codes.shape, jnp.float32))


def hash_uniform(idx: jax.Array, seed: jax.Array) -> jax.Array:
    """Counter-based uniform [0, 1) floats from element index + seed.

    A finalizer-style integer hash on the VPU (uint32 wraparound arithmetic):
    no gathers, no host PRNG round trip, bit-identical between the Pallas
    kernel and the jnp reference — which is what makes the stochastic-rounding
    parity tests exact (DESIGN.md §3).  ``pltpu.prng_random_bits`` would also
    work on TPU but has no interpret-mode lowering on CPU.
    """
    x = idx.astype(jnp.uint32) + seed.astype(jnp.uint32) * jnp.uint32(2654435761)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x21F0AAAD)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x735A2D97)
    x = x ^ (x >> 15)
    # Top-of-24-bits mantissa -> exactly representable uniform in [0, 1).
    # Via int32 (exact below 2^24): Mosaic has no uint32 -> f32 cast.
    return ((x >> 8).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(1.0 / (1 << 24)))


def element_indices(n_rows: int, n_cols: int, row_offset) -> jax.Array:
    """Global flat element index for a (n_rows, n_cols) tile whose first row
    is ``row_offset`` in the full block domain. uint32, wraps harmlessly."""
    r = jax.lax.broadcasted_iota(jnp.uint32, (n_rows, n_cols), 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, (n_rows, n_cols), 1)
    off = jnp.asarray(row_offset).astype(jnp.uint32)
    return (off + r) * jnp.uint32(n_cols) + c


def stochastic_codes(x_norm: jax.Array, codes: jax.Array, q_near: jax.Array,
                     q_other: jax.Array, other: jax.Array,
                     u: jax.Array) -> jax.Array:
    """Pick the far neighbour with probability proportional to proximity.

    Shared verbatim by the Pallas kernels and the jnp reference so both
    produce identical codes for identical uniforms."""
    span = jnp.abs(q_other - q_near)
    p_other = jnp.where(span > 0,
                        jnp.abs(x_norm - q_near) / jnp.where(span > 0, span, 1.0),
                        0.0)
    return jnp.where(u < p_other, other, codes)


def block_requantize(x: jax.Array, bounds, qmap=None,
                     random_u: jax.Array | None = None,
                     max_code: int = CODEBOOK_SIZE - 1
                     ) -> tuple[jax.Array, jax.Array]:
    """Per-row absmax normalize + encode. x: (R, B) f32 ->
    (codes int32 (R, B), absmax f32 (R, 1)).

    With ``random_u`` (uniforms in [0, 1), same shape as x) the encode is
    stochastic: round to the nearer/farther neighbouring code with
    probability proportional to proximity (paper App H). ``qmap`` is
    required in that case for the neighbour lookups.  ``max_code`` is the
    highest valid code (2^bits - 1 for k-bit codebooks); deterministic
    encode respects it by construction via the +inf boundary padding."""
    n_levels = max_code + 1
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax, 1.0)
    x_norm = x / scale
    codes = encode(x_norm, bounds, n_levels)
    if random_u is not None:
        q_near = decode(codes, qmap, n_levels)
        direction = jnp.where(x_norm > q_near, 1, -1)
        other = jnp.clip(codes + direction, 0, max_code)
        q_other = decode(other, qmap, n_levels)
        codes = stochastic_codes(x_norm, codes, q_near, q_other, other, random_u)
    return codes, absmax
