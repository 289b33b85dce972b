"""Compile-only checks of the Pallas kernels for a TPU v5e, at real widths.

No chip is needed: the TPU compiler compiles for a *described* v5e:2x2
topology, so Mosaic refuses here what it would refuse on the chip (a
value-level ``dynamic_slice``, a 2-D -> 1-D shape cast, a scalar ``pow``,
a uint32 -> f32 cast — each of which once kept every kernel off the chip).
Nothing runs; results and times need the chip (``chip_smoke.py``).

The topology is described inside a module fixture (never at import time),
so pytest-xdist workers all collect the same tests and only the worker
given this file loads the TPU library.  The persistent compilation cache is
off around these compiles: an executable for a described device is written
but cannot be read back without the chip.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import qmap as qmap_lib
from repro.core.lowbit import PackedCodes
from repro.kernels import ops, paged_kv  # ops first: the package's import root
from repro.kernels import newton_schulz as ns

# paper-lm-209m's FFN weight (1024 x 8192) in 2048-wide quantization blocks.
N_BLOCKS, BLOCK = 4096, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_on_chip(one_chip, no_compile_cache):
    """fn, *shapes -> compiled HLO text; asserts a Pallas kernel is in it."""
    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text, "no Pallas kernel in the program"
        return text
    return run


def _qmap(bits=8, signed=True):
    return jnp.asarray(qmap_lib.get_qmap("dynamic", signed, bits=bits))


def test_blockwise_quant_dequant_compile(compile_on_chip):
    q = _qmap()
    compile_on_chip(lambda x: ops.quantize_blockwise(x, q, impl="pallas"),
                    ((N_BLOCKS, BLOCK), jnp.float32))
    compile_on_chip(
        lambda c, a: ops.dequantize_blockwise(c, a, q, impl="pallas"),
        ((N_BLOCKS, BLOCK), jnp.uint8), ((N_BLOCKS,), jnp.float32))


@pytest.mark.parametrize("algo,bits_m,stochastic", [
    ("adamw", 8, True), ("adam", 4, False), ("lamb", 8, False)])
def test_fused_update_compile(compile_on_chip, algo, bits_m, stochastic):
    """adam/adamw at 8 bits and state_bits=(4, 8), and lamb with its norm
    prologue (a kernel of its own, which decodes both states): the fused
    update at one real weight's block count."""
    qm, qr = _qmap(bits_m), _qmap(8, signed=False)

    def step(p, g, cm, am, cr, ar):
        if bits_m != 8:
            cm = PackedCodes(cm, bits_m, BLOCK)
        return ops.fused_update(algo, p, g, cm, am, cr, ar, qm, qr, lr=1e-3,
                                step=3.0, stochastic=stochastic,
                                impl="pallas")

    text = compile_on_chip(step, ((N_BLOCKS, BLOCK), jnp.float32),
                           ((N_BLOCKS, BLOCK), jnp.float32),
                           ((N_BLOCKS, BLOCK * bits_m // 8), jnp.uint8),
                           ((N_BLOCKS,), jnp.float32),
                           ((N_BLOCKS, BLOCK), jnp.uint8),
                           ((N_BLOCKS,), jnp.float32))
    kernels = 2 if algo == "lamb" else 1
    assert text.count('custom_call_target="tpu_custom_call"') == kernels


def test_newton_schulz_compile(compile_on_chip):
    """The gram and apply kernels at m = 1024 (d_model of paper-lm-209m)."""
    m, n = 1024, 4096
    compile_on_chip(lambda x: ns._gram(x, ns.TILE_N, "pallas"),
                    ((m, n), jnp.float32))
    compile_on_chip(lambda x, b: ns._ns_apply(x, b, ns.NS_COEFFS[0],
                                              ns.TILE_N, "pallas"),
                    ((m, n), jnp.float32), ((m, m), jnp.float32))


def test_paged_kv_gather_compile(compile_on_chip):
    """The paged gather at 8 and 4 bits, stablelm-1.6b's KV geometry
    (32 kv heads x head_dim 64, 16-token pages)."""
    kv, dh, page = 32, 64, 16
    for bits in (8, 4):
        compile_on_chip(
            lambda c, a, t: paged_kv.gather_pages(
                c, a, t, bits=bits, dtype=jnp.bfloat16, impl="pallas"),
            ((512, page, kv, dh * bits // 8), jnp.uint8),
            ((512, page, kv), jnp.float32), ((8, 32), jnp.int32))


@pytest.mark.parametrize("kv,dh", [(1, 256), (8, 128)])
def test_paged_kv_gather_compile_head_layouts(compile_on_chip, kv, dh):
    """The paged gather for other kv-head layouts (one head of 256, eight
    of 128): head dims past one vreg row, and few heads per page."""
    for bits in (8, 4):
        compile_on_chip(
            lambda c, a, t: paged_kv.gather_pages(
                c, a, t, bits=bits, dtype=jnp.bfloat16, impl="pallas"),
            ((64, 16, kv, dh * bits // 8), jnp.uint8),
            ((64, 16, kv), jnp.float32), ((4, 8), jnp.int32))
