"""k-bit code-format subsystem for bit-packed optimizer states (DESIGN.md §9).

The paper's 8-bit block-wise states are one point on a memory/precision
curve; this package generalizes the code format to any bitwidth
b ∈ {4, 5, 6, 8}:

  * :mod:`repro.core.qmap` generates the dynamic/linear/quantile codebooks
    at 2^b levels (``get_qmap(name, signed, bits=b)``);
  * :class:`CodeFormat` bundles (bits, signedness, qmap name) per state
    slot and owns level-count/zero-code/byte accounting;
  * :class:`PackedCodes` is the storage container: sub-byte codes are
    bit-packed into uint8 words (two 4-bit codes per byte, big-endian
    bitstream for 5/6-bit), with pure-JAX :func:`pack_codes` /
    :func:`unpack_codes` that the Pallas kernels reuse verbatim so the
    fused path never materializes unpacked codes in HBM.

Everything above this layer (kernel registry, optimizer engine, checkpoint,
sharding) treats a state slot as (codes-container, absmax) and dispatches on
``isinstance(codes, PackedCodes)``.
"""
from repro.core.lowbit.format import CodeFormat
from repro.core.lowbit.packing import (PACK_LAYOUT, SUPPORTED_BITS,
                                       PackedCodes, pack_codes, packed_width,
                                       unpack_codes, unwrap_codes)

__all__ = [
    "CodeFormat", "PACK_LAYOUT", "PackedCodes", "SUPPORTED_BITS", "pack_codes",
    "packed_width", "unpack_codes", "unwrap_codes",
]
