"""Bit-packing of sub-byte optimizer-state codes into uint8 words.

Layout (lane-chunked bitstream): a row of N b-bit codes is cut into
``k = 8 / gcd(b, 8)`` contiguous lane chunks ``C_0 .. C_{k-1}`` of
``G = N / k`` codes, and its ``N*b/8`` bytes into ``m = b*k/8`` chunks
``P_0 .. P_{m-1}`` of ``G`` bytes.  Position ``j`` of every chunk forms one
group: codes ``C_0[j] .. C_{k-1}[j]`` are written MSB-first into a
big-endian bitstream of ``k*b`` bits, and byte ``P_i[j]`` holds stream bits
``[8i, 8i+8)`` with stream bit 8i at the byte's bit 7.  For b = 4 this is
``P_0 = C_0 << 4 | C_1`` (first half of the row in the high nibbles); for
b = 5/6 codes straddle byte boundaries, which the bitstream formulation
handles uniformly.  A row of N codes packs to exactly ``N*b/8`` bytes (N
must be a multiple of k; every supported quantization block size is).

``pack_codes`` / ``unpack_codes`` are pure jnp on 2-D tiles — static lane
slices, shifts, masks and a lane concatenation, no gathers and no reshapes
— so the *same functions* run inside the Pallas fused-update kernel
(unpack → dequant → update → requant → pack in VMEM) and on the XLA
reference path.  Packed codes parity between the kernels and
``impl="jnp"`` therefore holds by construction, the same contract the
8-bit kernels already follow (DESIGN.md §3, §9).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.errors import FormatError

SUPPORTED_BITS = (4, 5, 6, 8)
# Name of the byte layout above.  Serialized packed codes carry it (the
# checkpoint manifest) so bytes packed under any other layout are refused
# instead of being unpacked into scrambled codes.
PACK_LAYOUT = "lane_chunked"


def packed_width(n_codes: int, bits: int) -> int:
    """Bytes per row of ``n_codes`` b-bit codes (exact, no slack)."""
    if bits not in SUPPORTED_BITS:
        raise FormatError(f"bits={bits} unsupported; choose from "
                          f"{SUPPORTED_BITS}")
    if (n_codes * bits) % 8 != 0:
        raise FormatError(f"{n_codes} codes of {bits} bits do not fill "
                          f"whole bytes")
    return (n_codes * bits) // 8


def _overlaps(bits: int):
    """(code chunk c, byte chunk i, width, code shift, byte shift) for every
    run of stream bits shared by code chunk c and byte chunk i: the run is
    ``(C_c >> code_shift) & mask`` in the code and sits at ``<< byte_shift``
    in the byte."""
    k = 8 // math.gcd(bits, 8)
    runs = []
    for c in range(k):
        lo_c, hi_c = c * bits, (c + 1) * bits
        for i in range(lo_c // 8, (hi_c - 1) // 8 + 1):
            lo, hi = max(lo_c, 8 * i), min(hi_c, 8 * i + 8)
            runs.append((c, i, hi - lo, hi_c - hi, 8 * i + 8 - hi))
    return k, runs


def pack_codes(codes: jax.Array, bits: int) -> jax.Array:
    """(..., N) integer codes in [0, 2^bits) -> (..., N*bits/8) uint8."""
    if bits == 8:
        return codes.astype(jnp.uint8)
    n = codes.shape[-1]
    w = packed_width(n, bits)
    k, runs = _overlaps(bits)
    g = n // k
    c = codes.astype(jnp.int32)
    chunks = [c[..., j * g:(j + 1) * g] for j in range(k)]
    out = [None] * (w // g)
    for ci, i, width, cshift, bshift in runs:
        piece = ((chunks[ci] >> cshift) & ((1 << width) - 1)) << bshift
        out[i] = piece if out[i] is None else out[i] | piece
    packed = out[0] if len(out) == 1 else jnp.concatenate(out, axis=-1)
    return packed.astype(jnp.uint8)


def unpack_codes(packed: jax.Array, bits: int) -> jax.Array:
    """(..., W) uint8 words -> (..., W*8/bits) int32 codes in [0, 2^bits)."""
    if bits == 8:
        return packed.astype(jnp.int32)
    if bits not in SUPPORTED_BITS:
        raise FormatError(f"bits={bits} unsupported; choose from "
                          f"{SUPPORTED_BITS}")
    w = packed.shape[-1]
    k, runs = _overlaps(bits)
    g = w * 8 // bits // k
    b = packed.astype(jnp.int32)
    planes = [b[..., i * g:(i + 1) * g] for i in range(w // g)]
    out = [None] * k
    for ci, i, width, cshift, bshift in runs:
        piece = ((planes[i] >> bshift) & ((1 << width) - 1)) << cshift
        out[ci] = piece if out[ci] is None else out[ci] | piece
    return out[0] if k == 1 else jnp.concatenate(out, axis=-1)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedCodes:
    """Bit-packed codes for one state tensor in the flat block domain.

    packed : (n_blocks, n_codes*bits/8) uint8 — the only array child, so
             sharding/checkpoint trees see exactly one leaf per container
             and shard its *block-count* axis (dim 0), never the byte axis.
    bits   : static bitwidth of each code (4/5/6; 8-bit states stay plain
             uint8 arrays and never enter this container).
    n_codes: static logical codes per row (= the quantization block size).
    """

    packed: jax.Array
    bits: int
    n_codes: int

    def tree_flatten(self):
        return (self.packed,), (self.bits, self.n_codes)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)

    @classmethod
    def from_codes(cls, codes: jax.Array, bits: int) -> "PackedCodes":
        return cls(pack_codes(codes, bits), bits, int(codes.shape[-1]))

    def unpack(self) -> jax.Array:
        """-> (n_blocks, n_codes) int32 codes."""
        return unpack_codes(self.packed, self.bits)

    @property
    def shape(self) -> tuple:
        """Logical (unpacked) code-array shape."""
        return (*self.packed.shape[:-1], self.n_codes)

    def nbytes(self) -> int:
        return int(self.packed.size)


def unwrap_codes(codes):
    """One state-slot codes container -> ``(raw, bits, n_codes)``.

    ``raw`` is the stored uint8 array, ``bits`` the code bitwidth (8 for
    plain arrays), ``n_codes`` the logical per-row code count for packed
    containers and None for plain arrays (the re-wrap sentinel).  The one
    shared unwrap point for every layer that strips ``PackedCodes`` at a
    kernel/shard_map boundary (ops.fused_update, ops.segment_tensor_scales,
    the partitioned span dispatch)."""
    if isinstance(codes, PackedCodes):
        return codes.packed, codes.bits, codes.n_codes
    return codes, 8, None
