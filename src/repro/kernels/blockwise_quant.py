"""Pallas TPU kernel: block-wise 8-bit quantization (paper §2.1).

The Pallas tile is aligned to the quantization block: input is
``(n_blocks, B)`` and each grid step processes ``ROWS`` whole blocks, so the
per-block absmax is a row reduction inside one VMEM tile — no cross-core
communication, which is exactly the paper's argument for block-wise
normalization, mapped onto the TPU memory hierarchy (HBM -> VMEM -> VREG).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common

DEFAULT_ROWS = common.DEFAULT_ROWS  # quantization blocks per grid step


def _quant_kernel(x_ref, bounds_ref, codes_ref, absmax_ref):
    x = x_ref[...].astype(jnp.float32)              # (ROWS, B)
    codes, absmax = common.block_requantize(x, bounds_ref)
    codes_ref[...] = codes.astype(jnp.uint8)
    absmax_ref[...] = absmax                        # (ROWS, 1)


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def quantize_blockwise(
    x: jax.Array,
    codebook: jax.Array,
    *,
    rows: int = DEFAULT_ROWS,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """(n_blocks, B) -> (codes uint8 (n_blocks, B), absmax f32 (n_blocks,)).

    n_blocks must be a multiple of ``rows`` (ops.py pads).  ``interpret``
    has no default: callers say whether they want the chip kernel.
    """
    n_blocks, bsz = x.shape
    assert n_blocks % rows == 0, (n_blocks, rows)
    bounds = common.padded_bounds(codebook)
    grid = (n_blocks // rows,)
    codes, absmax = pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, bsz), lambda i: (i, 0)),
            common.table_spec(),
        ],
        out_specs=[
            pl.BlockSpec((rows, bsz), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, bsz), jnp.uint8),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, bounds)
    return codes, absmax[:, 0]
