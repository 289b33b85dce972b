"""Plain reference of Adam with block-wise 8-bit or 32-bit state.

The 8-bit state follows the paper ("8-bit Optimizers via Block-wise
Quantization", Dettmers et al., ICLR 2022, §2): each tensor is flattened,
cut into blocks of ``block_size`` elements, normalised by its block's
absolute maximum and stored as the index of the nearest value of the
dynamic (tree) map, signed for the first moment and unsigned for the
second.  An update dequantizes, runs the 32-bit Adam update and
requantizes.  As in the paper's released implementation, tensors under
``min_quant_size`` elements and the embedding keep 32-bit state.

Codes are decoded and encoded by comparisons against the map, in chunks of
rows: plain elementwise work with no table gathers.  Nothing here comes
from the program under test.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ROWS_PER_CHUNK = 64


@functools.lru_cache(maxsize=None)
def dynamic_map(signed: bool) -> np.ndarray:
    """The 8-bit dynamic tree map: 7 exponent levels 10^(i-6), with
    2^i (signed) or 2^(i+1) (unsigned) linear fraction steps over [0.1, 1]
    each, plus 0 and 1; sorted, 256 float32 values."""
    values = []
    for i in range(7):
        n = 2 ** i if signed else 2 ** (i + 1)
        edges = np.linspace(0.1, 1.0, n + 1)
        mids = (edges[:-1] + edges[1:]) / 2.0 * 10.0 ** (i - 6)
        values += list(mids)
        if signed:
            values += list(-mids)
    values += [0.0, 1.0]
    out = np.sort(np.asarray(values, np.float32))
    if out.shape != (256,):
        raise AssertionError(f"dynamic map has {out.shape[0]} values")
    return out


def _chunked(fn, x):
    """Apply ``fn`` to (ROWS_PER_CHUNK, B) slices of x (n_blocks, B)."""
    n = x.shape[0]
    pad = (-n) % ROWS_PER_CHUNK
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    out = jax.lax.map(fn, xp.reshape(-1, ROWS_PER_CHUNK, x.shape[1]))
    return out.reshape(-1, x.shape[1])[:n]


def decode(codes, absmax, qmap):
    """(n_blocks, B) uint8 codes -> f32 values."""
    q = jnp.asarray(qmap)
    levels = jnp.arange(256, dtype=jnp.int32)

    def dec(c):
        hit = c.astype(jnp.int32)[..., None] == levels
        return jnp.sum(jnp.where(hit, q, 0.0), axis=-1)

    return _chunked(dec, codes) * absmax[:, None]


def encode(x, qmap):
    """(n_blocks, B) f32 -> (codes uint8, absmax f32): nearest map value
    of each element over its block's absmax."""
    q = jnp.asarray(qmap)
    mids = (q[1:] + q[:-1]) / 2.0
    absmax = jnp.max(jnp.abs(x), axis=-1)
    xn = x / jnp.where(absmax > 0, absmax, 1.0)[:, None]

    def enc(v):
        return jnp.sum((v[..., None] > mids).astype(jnp.int32), axis=-1)

    return _chunked(enc, xn).astype(jnp.uint8), absmax


def to_blocks(x, block_size):
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % block_size
    return jnp.pad(flat, (0, pad)).reshape(-1, block_size)


def from_blocks(b, shape):
    n = int(np.prod(shape))
    return b.reshape(-1)[:n].reshape(shape)


class BlockwiseAdam:
    """Adam (decoupled weight decay) over a dict of named f32 leaves."""

    def __init__(self, *, bits, lr, beta1=0.9, beta2=0.999, eps=1e-8,
                 weight_decay=0.0, block_size=2048, min_quant_size=4096,
                 grad_clip=1.0):
        if bits not in (8, 32):
            raise ValueError(f"bits must be 8 or 32, got {bits}")
        self.bits, self.lr, self.beta1, self.beta2 = bits, lr, beta1, beta2
        self.eps, self.wd, self.block = eps, weight_decay, block_size
        self.min_quant, self.grad_clip = min_quant_size, grad_clip
        self.qm, self.qr = dynamic_map(True), dynamic_map(False)
        # the map the stored second moment is read back with; the
        # control's second-moment fault shifts it by one code
        self.qr_read = self.qr

    def quantized(self, path: str, shape) -> bool:
        if self.bits == 32 or int(np.prod(shape)) < self.min_quant:
            return False
        return not any(t in path.lower() for t in ("embed", "wte", "wpe"))

    def init(self, params: dict) -> dict:
        state = {}
        for path, p in params.items():
            if self.quantized(path, p.shape):
                nb = -(-p.size // self.block)
                z8 = jnp.zeros((nb, self.block), jnp.uint8)
                z = jnp.zeros((nb,), jnp.float32)
                state[path] = {"cm": z8, "am": z, "cr": z8, "ar": z}
            else:
                state[path] = {"m": jnp.zeros_like(p), "r": jnp.zeros_like(p)}
        return state

    def first_moment(self, path: str, st: dict, shape) -> jax.Array:
        """The stored first moment of one leaf, decoded, in param shape."""
        if "m" in st:
            return st["m"]
        return from_blocks(decode(st["cm"], st["am"], self.qm), shape)

    def step(self, params: dict, grads: dict, state: dict, t: int):
        """One update at step t (1-based) on clipped grads."""
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
        scale = jnp.minimum(1.0, self.grad_clip / jnp.maximum(gnorm, 1e-12))
        new_p, new_s = {}, {}
        for path, p in params.items():
            new_p[path], new_s[path] = _leaf_update(
                self, p, grads[path] * scale, state[path], float(t))
        return new_p, new_s

    def __hash__(self):
        return id(self)


@functools.partial(jax.jit, static_argnums=(0,))
def _leaf_update(opt, p, g, st, t):
    b1, b2 = jnp.float32(opt.beta1), jnp.float32(opt.beta2)
    c1 = 1.0 - jnp.power(b1, jnp.float32(t))
    c2 = 1.0 - jnp.power(b2, jnp.float32(t))
    quant = "cm" in st
    if quant:
        gb = to_blocks(g, opt.block)
        pb = to_blocks(p, opt.block)
        m = decode(st["cm"], st["am"], opt.qm)
        r = decode(st["cr"], st["ar"], opt.qr_read)
    else:
        gb, pb, m, r = g, p, st["m"], st["r"]
    m2 = b1 * m + (1.0 - b1) * gb
    r2 = b2 * r + (1.0 - b2) * gb * gb
    u = (m2 / c1) / (jnp.sqrt(r2 / c2) + opt.eps) + opt.wd * pb
    p2 = pb - opt.lr * u
    if not quant:
        return p2, {"m": m2, "r": r2}
    cm, am = encode(m2, opt.qm)
    cr, ar = encode(r2, opt.qr)
    return from_blocks(p2, p.shape), {"cm": cm, "am": am, "cr": cr, "ar": ar}
