"""Pallas TPU kernel: block-wise 8-bit dequantization.

Codebook lookup is an exact lane gather from a codebook held in vector
registers (``common.decode``) — the TPU analogue of the CUDA
shared-memory LUT gather (DESIGN.md §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common

DEFAULT_ROWS = common.DEFAULT_ROWS


def _dequant_kernel(codes_ref, absmax_ref, qmap_ref, out_ref):
    codes = codes_ref[...].astype(jnp.int32)        # (ROWS, B)
    vals = common.decode(codes, qmap_ref)      # f32
    out_ref[...] = (vals * absmax_ref[...]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rows", "interpret", "dtype"))
def dequantize_blockwise(
    codes: jax.Array,
    absmax: jax.Array,
    codebook: jax.Array,
    *,
    rows: int = DEFAULT_ROWS,
    interpret: bool,
    dtype=jnp.float32,
) -> jax.Array:
    """(codes (n_blocks, B), absmax (n_blocks,)) -> values (n_blocks, B).
    ``interpret`` has no default: callers say whether they want the chip
    kernel."""
    n_blocks, bsz = codes.shape
    assert n_blocks % rows == 0, (n_blocks, rows)
    qmap = common.padded_qmap(codebook)
    grid = (n_blocks // rows,)
    out = pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, bsz), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            common.table_spec(),
        ],
        out_specs=pl.BlockSpec((rows, bsz), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks, bsz), dtype),
        interpret=interpret,
    )(codes, absmax[:, None], qmap)
    return out
