"""Shared in-kernel helpers for the 8-bit optimizer Pallas kernels.

TPU adaptation notes (DESIGN.md §3): the CUDA kernels of the paper use
per-thread binary search + shared-memory LUTs.  On TPU the codebook lives
in vector registers and both searches are lane gathers
(``jnp.take_along_axis`` along lanes, which Mosaic lowers to
``tpu.dynamic_gather``: an exact in-register permutation, so a looked-up
value is the codebook entry bit for bit):

  * codebook lookup: the 256-entry codebook is a ``(2, 128)`` lane table;
    each half is broadcast to a tile of lanes, and a 128-lane column of
    codes reads ``where(c >= 128, hi[c & 127], lo[c & 127])``.  Maps of at
    most 128 levels (k-bit) read the low half alone.
  * nearest-code search: a branch-free binary search over the sorted
    midpoint boundaries, ``idx += [x >= b[idx + 2^k - 1]] << k`` for
    k = bits-1 .. 0 — ``bits`` steps, the first a compare with one
    boundary — which equals ``sum_j [x >= b_j]`` (searchsorted, side
    'right') on every input, NaN included (-> 0).

Codebooks and boundaries are padded to 256 entries (boundary 256 = +inf)
and travel as ``(2, 128)`` f32 VMEM operands (``table_spec``), one lane
table each.  Work is cut into 128-lane columns: a gather reaches within
one vreg row only.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CODEBOOK_SIZE = 256
# Lanes of one vreg row: the reach of one lane gather, and the width of a
# codebook lane table's rows.
LANES = 128
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
    """Whole-array SMEM operand: the scalar vectors."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def table_spec() -> pl.BlockSpec:
    """A ``(2, 128)`` lane table in VMEM, the same block at every grid step
    (fetched once); takes any grid and scalar-prefetch arguments."""
    return pl.BlockSpec((CODEBOOK_SIZE // LANES, LANES), lambda *_: (0, 0))


def _lane_table(values: jax.Array, fill: float) -> jax.Array:
    """(n <= 256,) f32 -> (2, 128): padded with ``fill``, entry j at
    [j // 128, j % 128]."""
    pad = CODEBOOK_SIZE - values.shape[0]
    if pad:
        values = jnp.concatenate([values, jnp.full((pad,), fill, jnp.float32)])
    return values.reshape(CODEBOOK_SIZE // LANES, LANES)


def padded_bounds(codebook) -> jax.Array:
    """Midpoint decision boundaries padded with +inf to 256 entries, as a
    (2, 128) lane table.

    For an L-entry codebook (L = 2^bits <= 256) the L-1 real boundaries are
    followed by +inf padding; ``encode``'s search never reads past entry
    L-2, so it can only emit codes < L — the k-bit maps cap their code
    range for free."""
    cb = jnp.asarray(codebook, dtype=jnp.float32)
    return _lane_table((cb[1:] + cb[:-1]) * 0.5, jnp.inf)


def padded_qmap(codebook) -> jax.Array:
    """Codebook zero-padded to 256 entries, as a (2, 128) lane table.
    Padding entries are unreachable: codes from ``encode`` /
    ``block_requantize`` stay below the real level count."""
    return _lane_table(jnp.asarray(codebook, dtype=jnp.float32), 0.0)


def _by_lanes(fn, x: jax.Array) -> jax.Array:
    """``fn`` over the 128-lane columns of ``x`` (..., N), viewed as 2-D;
    the last column is zero-padded when N is not a multiple of 128."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    n = shape[-1]
    pad = -n % LANES
    if pad:
        x2 = jnp.concatenate([x2, jnp.zeros((x2.shape[0], pad), x2.dtype)],
                             axis=1)
    cols = [fn(x2[:, j:j + LANES]) for j in range(0, n + pad, LANES)]
    out = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
    return out[:, :n].reshape(shape)


def _gather(half: jax.Array, idx: jax.Array) -> jax.Array:
    """half[0, idx] for idx (R, 128) int32 in [0, 128): one lane gather."""
    table = jnp.broadcast_to(half, idx.shape)
    return jnp.take_along_axis(table, idx, axis=1, mode="promise_in_bounds")


def _halves(table, n_levels: int):
    """The lane table's two (1, 128) rows; no high half when every index
    is below 128.  ``table`` is a VMEM ref inside a kernel, an array
    outside."""
    lo = table[0:1, :]
    return lo, (table[1:2, :] if n_levels > LANES else None)


# The column searches are jitted so that a kernel traces each once: its
# 128-lane columns reuse the trace, and Mosaic inlines every call.
@functools.partial(jax.jit, static_argnames="n_levels")
def _search_column(x, first, lo, hi, n_levels: int):
    """``encode`` on one (R, 128) column; ``first`` is boundary top-1
    broadcast to the column's shape."""
    top = n_levels // 2
    # With a high half, step one picks the half and the rest searches
    # its lanes.
    up = x >= first
    if hi is None:
        look = lambda j: _gather(lo, j)
        idx = jnp.where(up, top, 0)
    else:
        look = lambda j: jnp.where(up, _gather(hi, j), _gather(lo, j))
        idx = jnp.zeros(x.shape, jnp.int32)
    step = min(top, LANES) // 2
    while step:
        idx = idx + jnp.where(x >= look(idx + (step - 1)), step, 0)
        step //= 2
    return idx if hi is None else jnp.where(up, idx + LANES, idx)


@jax.jit
def _lookup_column(c, lo, hi):
    """``decode`` on one (R, 128) column."""
    if hi is None:
        return _gather(lo, c)
    i = c & (LANES - 1)
    return jnp.where(c >= LANES, _gather(hi, i), _gather(lo, i))


def encode(x_norm: jax.Array, bounds, n_levels: int = CODEBOOK_SIZE
           ) -> jax.Array:
    """Nearest-code indices for normalized values in [-1, 1].

    x_norm: (..., N) f32; bounds: the ``padded_bounds`` lane table;
    n_levels: 2^bits.  Returns int32 codes equal to ``sum_j [x >= b_j]``
    over the ``n_levels - 1`` real boundaries (searchsorted, side
    'right'), by a binary search of ``bits`` steps; the boundaries are
    sorted because every codebook is.
    """
    lo, hi = _halves(bounds, n_levels)
    # Step one compares every element with boundary top-1 (a low-half
    # entry), gathered once: Mosaic broadcasts no (1, 1) value over both
    # sublanes and lanes.
    rows = math.prod(x_norm.shape[:-1])
    first = _gather(lo, jnp.full((rows, LANES), n_levels // 2 - 1, jnp.int32))
    return _by_lanes(
        lambda x: _search_column(x, first, lo, hi, n_levels=n_levels), x_norm)


def decode(codes: jax.Array, qmap, n_levels: int = CODEBOOK_SIZE
           ) -> jax.Array:
    """Codebook lookup by lane gathers, exact.

    codes: (..., N) int32 in [0, n_levels); qmap: the ``padded_qmap`` lane
    table.  Maps of at most 128 levels read its low half alone.
    """
    lo, hi = _halves(qmap, n_levels)
    return _by_lanes(lambda c: _lookup_column(c, lo, hi), codes)


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
