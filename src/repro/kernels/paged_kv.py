"""Paged block-wise quantized KV cache: append + gather-dequant kernels.

The serving KV cache (DESIGN.md §17) stores keys/values in a fixed pool of
*pages*.  One page holds ``page_size`` token positions for every kv head of
one layer; each (position, head) row of ``Dh`` values is one quantization
block in the paper's scheme — normalized by its own absmax, nearest-code
encoded against a 2^bits dynamic codebook (``core.qmap``), and for
``bits < 8`` bit-packed along the head dim via ``core.lowbit.pack_codes``.

Storage per layer (``W = Dh * bits / 8`` bytes per row):

    codes : (n_pages, page_size, KV, W)  uint8
    absmax: (n_pages, page_size, KV)     f32

Two data paths, both independent of the page *allocator* (host-side, in
``repro.serve.kvcache``):

  * ``append_rows`` — quantize-on-append: one new (B, KV, Dh) row batch is
    encoded and scattered to per-slot (page, offset) destinations in a
    single XLA scatter; out-of-range page ids (inactive slots, the
    scheduler's sentinel) are dropped, not clamped, so no live page can be
    corrupted by a masked lane.
  * ``gather_pages`` — dequantize-on-attend: the physical pages of every
    slot's page table are gathered and decoded to (B, L, KV, Dh) values.
    ``impl="pallas"`` is the TPU kernel: the page table rides scalar
    prefetch (``PrefetchScalarGridSpec``) so each grid step DMAs exactly
    one physical page HBM->VMEM, and the codebook lookup is the lane
    gather every kernel in this package uses (common.decode).  Codes,
    absmax and output keep the cache's own layout, so XLA copies nothing
    around the kernel; one more lane gather moves each row's absmax from
    its head's lane onto the row (``_row_scale``).
    ``impl="jnp"`` is the XLA oracle; parity is exercised in
    tests/test_serve_paged.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import qmap as qmap_lib
from repro.core.lowbit import pack_codes, unpack_codes
from repro.errors import FormatError
from repro.kernels import common

KV_QMAP_NAME = "dynamic"
KV_BITS = (4, 8)


@functools.lru_cache(maxsize=8)
def _kv_qmap_np(bits: int = 8):
    return qmap_lib.get_qmap(KV_QMAP_NAME, True, bits=bits)


def kv_qmap(bits: int = 8) -> jax.Array:
    """The signed dynamic codebook used for every KV row (2^bits levels)."""
    return jnp.asarray(_kv_qmap_np(bits))


def packed_row_width(head_dim: int, bits: int) -> int:
    """Stored bytes per (position, head) row of ``head_dim`` values."""
    if bits not in KV_BITS:
        raise FormatError(f"kv bits={bits} unsupported; choose from "
                          f"{KV_BITS}")
    if (head_dim * bits) % 8 != 0:
        raise FormatError(f"head_dim={head_dim} at {bits}-bit KV does not "
                          f"fill whole bytes")
    return (head_dim * bits) // 8


def bits_of(head_dim: int, row_width: int) -> int:
    """Recover the code bitwidth from array shapes (8 * W / Dh) — the paged
    cache carries no dtype tag, the packing ratio IS the format."""
    bits = (row_width * 8) // head_dim
    if bits not in KV_BITS or packed_row_width(head_dim, bits) != row_width:
        raise FormatError(f"row width {row_width} is not a supported "
                          f"packing of head_dim {head_dim}")
    return bits


# ------------------------------------------------------------ row quantize

def quantize_rows(x: jax.Array, bits: int = 8
                  ) -> tuple[jax.Array, jax.Array]:
    """x: (..., Dh) -> (codes uint8 (..., W), absmax f32 (...,)).

    Block = one head row (absmax per (..., head)); same math as the
    contiguous int8 KV path (layers.kv_quantize) at bits=8, so paged and
    contiguous caches quantize identically by construction.
    """
    cb = kv_qmap(bits)
    x = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.where(absmax > 0, absmax, 1.0)
    bounds = (cb[1:] + cb[:-1]) * 0.5
    codes = jnp.searchsorted(bounds, x / scale[..., None], side="right")
    if bits == 8:
        return codes.astype(jnp.uint8), absmax
    return pack_codes(codes.astype(jnp.int32), bits), absmax


def dequantize_rows(codes: jax.Array, absmax: jax.Array, dtype,
                    bits: int = 8) -> jax.Array:
    """(codes (..., W), absmax (...,)) -> values (..., Dh) in ``dtype``.

    A narrower float ``dtype`` is rounded explicitly (``reduce_precision``)
    before the cast: XLA may drop a cast pair such as f32->bf16->f32 under
    excess precision, while the Pallas gather writes real ``dtype`` values
    to memory, and the two impls must agree exactly."""
    cb = kv_qmap(bits)
    idx = unpack_codes(codes, bits) if bits != 8 else codes.astype(jnp.int32)
    vals = cb[idx] * absmax[..., None]
    fi = jnp.finfo(dtype)
    if fi.bits < 32:
        vals = jax.lax.reduce_precision(vals, exponent_bits=fi.nexp,
                                        mantissa_bits=fi.nmant)
    return vals.astype(dtype)


# ----------------------------------------------------------------- append

def append_rows(pages_codes: jax.Array, pages_absmax: jax.Array,
                rows: jax.Array, page_ids: jax.Array, offsets: jax.Array,
                bits: int) -> tuple[jax.Array, jax.Array]:
    """Quantize-on-append one token row per slot.

    pages_codes : (n_pages, page_size, KV, W) uint8
    pages_absmax: (n_pages, page_size, KV) f32
    rows        : (B, KV, Dh) new k or v rows (post-rope)
    page_ids    : (B,) int32 physical destination page per slot; any id
                  outside [0, n_pages) is DROPPED (inactive-slot sentinel)
    offsets     : (B,) int32 position within the page
    """
    codes, absmax = quantize_rows(rows, bits)
    return (pages_codes.at[page_ids, offsets].set(codes, mode="drop"),
            pages_absmax.at[page_ids, offsets].set(absmax, mode="drop"))


# ----------------------------------------------------------- gather-dequant

def _gather_kernel(table_ref, codes_ref, absmax_ref, qmap_ref, out_ref,
                   *, bits: int):
    """One grid step = one (slot, logical page) cell: the physical page
    selected by the scalar-prefetched table is already in VMEM (index_map
    DMA); unpack -> lane-gather decode -> scale."""
    del table_ref  # consumed by the index maps
    codes = codes_ref[0]                         # (page, KV, W) uint8
    if bits != 8:
        codes = unpack_codes(codes, bits)        # (page, KV, Dh)
    vals = common.decode(codes.astype(jnp.int32), qmap_ref,
                         n_levels=2 ** bits)
    out_ref[0] = (vals * _row_scale(absmax_ref[0], vals.shape)
                  ).astype(out_ref.dtype)


def _row_scale(absmax: jax.Array, shape) -> jax.Array:
    """(page, KV) absmax -> (page, KV, Dh): row (p, k) holds absmax[p, k] in
    every lane.  The KV heads sit on lanes, and a row needs its head on
    sublanes: a lane gather of each position's (KV,) vector by the row's
    own head index moves it there."""
    page, kv, dh = shape
    a = jnp.concatenate(
        [absmax, jnp.zeros((page, common.LANES - kv), absmax.dtype)], axis=1)
    a = jnp.broadcast_to(a[:, None, :], (page, kv, common.LANES))
    a = a.reshape(page * kv, common.LANES)
    head = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0) % kv
    a = jnp.take_along_axis(a, head, axis=1, mode="promise_in_bounds")
    return jnp.broadcast_to(a[:, :1], (page * kv, dh)).reshape(shape)


def _gather_pallas(pages_codes, pages_absmax, page_table, *, bits, dtype,
                   interpret: bool):
    n_pages, page, KV, W = pages_codes.shape
    B, P = page_table.shape
    Dh = (W * 8) // bits
    # Clip on the host side of the kernel: an unallocated (-1) table entry
    # must still name a DMA-able page; its rows are masked downstream by
    # the per-slot length mask.
    table = jnp.clip(page_table, 0, n_pages - 1).astype(jnp.int32)
    qmap = common.padded_qmap(kv_qmap(bits))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, page, KV, W),
                         lambda b, p, t: (t[b, p], 0, 0, 0)),
            pl.BlockSpec((1, page, KV), lambda b, p, t: (t[b, p], 0, 0)),
            common.table_spec(),
        ],
        out_specs=pl.BlockSpec((1, page, KV, Dh),
                               lambda b, p, t: (b, p, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_gather_kernel, bits=bits),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, P * page, KV, Dh), dtype),
        interpret=interpret,
    )(table, pages_codes, pages_absmax, qmap)


def _gather_jnp(pages_codes, pages_absmax, page_table, *, bits, dtype):
    n_pages, page, KV, W = pages_codes.shape
    B, P = page_table.shape
    table = jnp.clip(page_table, 0, n_pages - 1)
    codes = pages_codes[table]                   # (B, P, page, KV, W)
    absmax = pages_absmax[table]                 # (B, P, page, KV)
    vals = dequantize_rows(codes, absmax, dtype, bits)
    Dh = (W * 8) // bits
    return vals.reshape(B, P * page, KV, Dh)


@functools.partial(jax.jit, static_argnames=("bits", "dtype", "impl"))
def gather_pages(pages_codes: jax.Array, pages_absmax: jax.Array,
                 page_table: jax.Array, *, bits: int, impl: str,
                 dtype=jnp.float32) -> jax.Array:
    """Gather + dequantize every slot's pages.

    page_table: (B, P) int32 physical page per logical page (-1 =
    unallocated; gathered-but-masked, see DESIGN.md §17).  Returns
    (B, P*page_size, KV, Dh) values in ``dtype``.
    """
    if impl == "jnp":
        return _gather_jnp(pages_codes, pages_absmax, page_table,
                           bits=bits, dtype=dtype)
    if impl in ("pallas", "interpret"):
        return _gather_pallas(pages_codes, pages_absmax, page_table,
                              bits=bits, dtype=dtype,
                              interpret=(impl == "interpret"))
    raise FormatError(f"unknown impl {impl!r}; have jnp|pallas|interpret")
