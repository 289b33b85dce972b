"""Transformer building blocks: norms, rotary, GQA attention (chunked
online-softmax for train/prefill; plain cache attention for decode), MLPs.

Everything is pure-functional: ``init_*`` returns ``(params, logical_specs)``
where specs mirror the param tree with tuples of logical axis names
(resolved to mesh PartitionSpecs by repro.sharding.rules).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.constrain import attn_score_dims, constrain
from repro.telemetry import tracing as _tracing

# --------------------------------------------------------------------- utils

def dense_init(key, shape, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else 1.0 / np.sqrt(shape[0])
    return jax.random.normal(key, shape, dtype) * scale


def xavier_uniform(key, shape, dtype=jnp.float32):
    fan_in, fan_out = shape[0], shape[-1]
    lim = np.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, dtype, -lim, lim)


# --------------------------------------------------------------------- norms

def init_norm(d: int, norm_type: str):
    p = {"scale": jnp.ones((d,), jnp.float32)}
    s = {"scale": ("embed",)}
    if norm_type == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
        s["bias"] = ("embed",)
    return p, s


def apply_norm(p, x, norm_type: str, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    if norm_type == "rmsnorm":
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        out = x32 * jax.lax.rsqrt(var + eps) * p["scale"]
    else:
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        out = (x32 - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return out.astype(x.dtype)


# -------------------------------------------------------------------- rotary

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq      # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                            # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------- int8 KV cache (extension)
# Beyond-paper: the paper's block-wise 8-bit quantizer applied to the KV
# cache (block = one head row of Dh values, absmax per (position, head)).
# Halves decode-cache HBM residency; enabled per-arch via
# cfg.kv_cache_bits == 8.  DESIGN.md §4, EXPERIMENTS.md §Perf D.
# The k-bit row quantizer itself lives in kernels/paged_kv.py (shared with
# the paged serving cache, DESIGN.md §17); these are the 8-bit-default
# wrappers the contiguous cache path keeps using.

def kv_quantize(x, bits: int = 8):
    """x: (..., Dh) -> (codes uint8 (..., Dh*bits/8), absmax f32 (...,))."""
    from repro.kernels import paged_kv
    return paged_kv.quantize_rows(x, bits)


def kv_dequantize(codes, absmax, dtype, bits: int = 8):
    from repro.kernels import paged_kv
    return paged_kv.dequantize_rows(codes, absmax, dtype, bits)


# ------------------------------------------------ paged KV serving context

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedContext:
    """Per-decode-step paged-cache context (DESIGN.md §17).

    page_table: (n_slots, max_pages_per_seq) int32 — physical page per
                logical page; -1 = unallocated (gathered-but-masked).
    positions : (n_slots,) int32 — index of the token being decoded this
                step per slot; -1 = inactive slot (its append is dropped
                and its attention masks every key).
    impl      : gather-dequant kernel implementation (static; "jnp" XLA
                oracle, "interpret"/"pallas" the Pallas kernel; no
                default — the serving engine passes the platform's path).
    """

    page_table: jax.Array
    positions: jax.Array
    impl: str

    def tree_flatten(self):
        return (self.page_table, self.positions), (self.impl,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


# ----------------------------------------------------------------- attention

def init_attention(key, cfg):
    d, H, KV, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, H * Dh)),
        "wk": dense_init(ks[1], (d, KV * Dh)),
        "wv": dense_init(ks[2], (d, KV * Dh)),
        "wo": dense_init(ks[3], (H * Dh, d), scale=1.0 / np.sqrt(H * Dh)),
    }
    s = {
        "wq": ("embed", "heads"), "wk": ("embed", "kv_heads"),
        "wv": ("embed", "kv_heads"), "wo": ("heads", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * Dh,), jnp.float32)
        p["bk"] = jnp.zeros((KV * Dh,), jnp.float32)
        p["bv"] = jnp.zeros((KV * Dh,), jnp.float32)
        s["bq"], s["bk"], s["bv"] = ("heads",), ("kv_heads",), ("kv_heads",)
    return p, s


def _chunked_causal_attention(q, k, v, *, window: int, chunk: int):
    """Online-softmax attention, scanned over KV chunks (memory-bounded).

    q: (B, S, H, D), k/v: (B, S, KV, D) with KV | H (GQA). Causal; if
    ``window > 0`` additionally restricts to a sliding window (SWA) and only
    iterates KV chunks that can intersect the window of some query.
    Returns (B, S, H, D).
    """
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV                                   # query heads per kv head
    chunk = int(min(chunk, S))
    n_chunks = -(-S // chunk)
    pad = n_chunks * chunk - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    score_dims = attn_score_dims(KV, G, S)
    qh = (q.reshape(B, S, KV, G, D) * (D ** -0.5)).astype(jnp.float32)
    qh = qh.transpose(0, 2, 3, 1, 4)                  # (B, KV, G, S, D)
    qh = constrain(qh, *score_dims)
    q_pos = jnp.arange(S)

    def body(carry, idx):
        m_run, d_run, acc = carry
        k_c = jax.lax.dynamic_slice_in_dim(k, idx * chunk, chunk, axis=1)
        v_c = jax.lax.dynamic_slice_in_dim(v, idx * chunk, chunk, axis=1)
        kv_pos = idx * chunk + jnp.arange(chunk)
        # scores: (B, KV, G, S, C)
        scores = jnp.einsum("bkgsd,bckd->bkgsc", qh, k_c.astype(jnp.float32))
        scores = constrain(scores, *score_dims)
        mask = q_pos[:, None] >= kv_pos[None, :]                   # causal
        if window > 0:
            mask &= (q_pos[:, None] - kv_pos[None, :]) < window    # SWA
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
        m_new = jnp.maximum(m_run, jnp.max(scores, axis=-1))
        # guard: rows with no valid key yet keep m=-inf; exp(-inf - -inf)
        m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
        p_ = jnp.exp(scores - m_safe[..., None])
        p_ = jnp.where(mask[None, None, None], p_, 0.0)
        corr = jnp.where(jnp.isinf(m_run), 0.0, jnp.exp(m_run - m_safe))
        d_new = d_run * corr + jnp.sum(p_, axis=-1)
        pv = jnp.einsum("bkgsc,bckd->bkgsd", p_, v_c.astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return (m_new, d_new, acc_new), None

    m0 = constrain(jnp.full((B, KV, G, S), -jnp.inf, jnp.float32), *score_dims[:4])
    d0 = constrain(jnp.zeros((B, KV, G, S), jnp.float32), *score_dims[:4])
    a0 = constrain(jnp.zeros((B, KV, G, S, D), jnp.float32), *score_dims[:4])
    # Recompute chunk scores in the backward instead of storing them: the
    # scan otherwise stacks (B,KV,G,S,C) f32 residuals per chunk via
    # dynamic-update-slice — measured as the dominant HBM traffic of every
    # train/prefill cell (EXPERIMENTS.md §Perf C1).
    body = jax.checkpoint(body)
    # SWA: only the last (window//chunk + 1) chunks can intersect any query's
    # window *relative to the final chunk*… queries span all positions, so all
    # chunks are needed; per-chunk masking already zeroes dead work. True
    # chunk-skipping needs q-blocking (see EXPERIMENTS.md §Perf).
    (m_f, d_f, acc), _ = jax.lax.scan(body, (m0, d0, a0), jnp.arange(n_chunks))
    out = acc / jnp.maximum(d_f[..., None], 1e-30)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, D)


def _decode_attention(q, k_cache, v_cache, cache_len):
    """Single-position attention over a (possibly ring) cache.

    q: (B, 1, H, D); k/v_cache: (B, eff, KV, D).  Slot validity: the ring
    holds exactly the last min(cache_len, eff) positions, all causally
    visible (the current token's kv is already written).  For full-attention
    caches eff == max_len and this reduces to ``slot < cache_len``.
    """
    B, _, H, D = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    eff = k_cache.shape[1]
    qh = (q.reshape(B, KV, G, D) * (D ** -0.5)).astype(jnp.float32)
    scores = jnp.einsum("bkgd,bckd->bkgc", qh, k_cache.astype(jnp.float32))
    mask = jnp.arange(eff) < jnp.minimum(cache_len, eff)
    scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgc,bckd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, D)


def _masked_decode_attention(q, k, v, valid):
    """Single-position attention with an explicit per-slot validity mask.

    q: (B, 1, H, D); k/v: (B, L, KV, D); valid: (B, L) bool.  Unlike
    ``_decode_attention`` the mask is 2-D (per-slot lengths differ under
    continuous batching) and an all-False row (inactive slot) yields zeros
    instead of NaN — the scheduler discards those logits, but they must not
    poison debug NaN-checks.
    """
    B, _, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qh = (q.reshape(B, KV, G, D) * (D ** -0.5)).astype(jnp.float32)
    scores = jnp.einsum("bkgd,bckd->bkgc", qh, k.astype(jnp.float32))
    m = valid[:, None, None, :]
    neg = jnp.finfo(jnp.float32).min
    scores = jnp.where(m, scores, neg)
    smax = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.where(m, jnp.exp(scores - smax), 0.0)
    denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bkgc,bckd->bkgd", p / denom, v.astype(jnp.float32))
    return out.reshape(B, 1, H, D)


def _paged_decode_attention(q, k, v, cfg, cache, paged):
    """Paged-KV decode (DESIGN.md §17): quantize-on-append the new k/v rows
    into the slot's current page, then gather-dequant every table page and
    attend under the per-slot length (and SWA window) mask.

    cache: {"k_codes": (n_pages, page, KV, W), "k_absmax": (n_pages, page,
    KV), "v_codes", "v_absmax"}; q/k/v: (B, 1, {H|KV}, Dh).
    Returns (out (B, 1, H, Dh), new_cache).
    """
    from repro.kernels import paged_kv

    n_pages, page = cache["k_codes"].shape[:2]
    bits = paged_kv.bits_of(cfg.head_dim, cache["k_codes"].shape[-1])
    pos = paged.positions                                # (B,) int32
    active = pos >= 0
    pos_c = jnp.maximum(pos, 0)
    B = pos.shape[0]
    # Destination (physical page, offset) of this step's row per slot;
    # inactive slots are pointed out of range so the scatter drops them.
    logical = pos_c // page
    ppage = paged.page_table[jnp.arange(B), logical]
    ppage = jnp.where(active & (ppage >= 0), ppage, n_pages)
    off = pos_c % page
    new_cache = dict(cache)
    with _tracing.annotate("kv_append"):
        for name, row in (("k", k), ("v", v)):
            new_cache[f"{name}_codes"], new_cache[f"{name}_absmax"] = \
                paged_kv.append_rows(cache[f"{name}_codes"],
                                     cache[f"{name}_absmax"],
                                     row[:, 0], ppage, off, bits)
    dt = q.dtype
    with _tracing.annotate("kv_gather"):
        k_all, v_all = (paged_kv.gather_pages(
            new_cache[f"{name}_codes"], new_cache[f"{name}_absmax"],
            paged.page_table, bits=bits, dtype=dt, impl=paged.impl)
            for name in ("k", "v"))
    L = k_all.shape[1]
    idx = jnp.arange(L)[None, :]
    valid = active[:, None] & (idx <= pos_c[:, None])
    if cfg.attn_type == "swa" and cfg.window:
        valid &= idx > (pos_c[:, None] - cfg.window)
    out = _masked_decode_attention(q, k_all, v_all, valid)
    return out, new_cache


def _write_prefill_cache(buf, new):
    """Store S new kv rows into a ring buffer of physical size eff, such that
    position p lives in slot p % eff (static S)."""
    S = new.shape[1]
    eff = buf.shape[1]
    new = new.astype(buf.dtype)
    if S >= eff:
        last = new[:, S - eff:]
        return jnp.roll(last, (S - eff) % eff, axis=1)
    return jax.lax.dynamic_update_slice_in_dim(buf, new, 0, axis=1)


def apply_attention(p, x, cfg, *, positions, cache=None, cache_len=None,
                    paged=None):
    """x: (B, S, d).

    cache=None            -> train forward, no state io.
    cache given, S == 1   -> decode: write kv at slot (cache_len-1) % eff;
                             with ``paged`` (a PagedContext) the cache is
                             the shared quantized page pool and per-slot
                             positions/page tables drive append + attend.
    cache given, S > 1    -> prefill: full chunked attention + bulk cache fill.
    Returns (out, new_cache)."""
    B, S, d = x.shape
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = x.dtype
    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = q.reshape(B, S, H, Dh)
    k = k.reshape(B, S, KV, Dh)
    v = v.reshape(B, S, KV, Dh)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    window = cfg.window if cfg.attn_type == "swa" else 0
    quant_cache = cache is not None and "k_codes" in cache
    if paged is not None and cache is not None and S == 1:
        out, new_cache = _paged_decode_attention(q, k, v, cfg, cache, paged)
        out = constrain(out.reshape(B, S, H * Dh).astype(dt), "dp", None, "tp")
        return out @ p["wo"].astype(dt), new_cache
    if cache is None:
        out = _chunked_causal_attention(q, k, v, window=window,
                                        chunk=cfg.attn_chunk)
        new_cache = None
    elif S == 1 and quant_cache:
        eff = cache["k_codes"].shape[1]
        idx = (cache_len - 1) % eff
        new_cache = dict(cache)
        for name, row in (("k", k), ("v", v)):
            codes, absmax = kv_quantize(row)        # (B,1,KV,D)/(B,1,KV)
            new_cache[f"{name}_codes"] = jax.lax.dynamic_update_slice_in_dim(
                cache[f"{name}_codes"], codes, idx, axis=1)
            new_cache[f"{name}_absmax"] = jax.lax.dynamic_update_slice_in_dim(
                cache[f"{name}_absmax"], absmax, idx, axis=1)
        k_cache = kv_dequantize(new_cache["k_codes"], new_cache["k_absmax"], dt)
        v_cache = kv_dequantize(new_cache["v_codes"], new_cache["v_absmax"], dt)
        out = _decode_attention(q, k_cache, v_cache, cache_len)
    elif S == 1:
        eff = cache["k"].shape[1]
        idx = (cache_len - 1) % eff
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), idx, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), idx, axis=1)
        out = _decode_attention(q, k_cache, v_cache, cache_len)
        new_cache = {"k": k_cache, "v": v_cache}
    elif quant_cache:
        out = _chunked_causal_attention(q, k, v, window=window,
                                        chunk=cfg.attn_chunk)
        new_cache = {}
        for name, row in (("k", k), ("v", v)):
            codes, absmax = kv_quantize(row)
            new_cache[f"{name}_codes"] = _write_prefill_cache(
                cache[f"{name}_codes"], codes)
            new_cache[f"{name}_absmax"] = _write_prefill_cache(
                cache[f"{name}_absmax"], absmax)
    else:
        out = _chunked_causal_attention(q, k, v, window=window,
                                        chunk=cfg.attn_chunk)
        new_cache = {"k": _write_prefill_cache(cache["k"], k),
                     "v": _write_prefill_cache(cache["v"], v)}
    out = constrain(out.reshape(B, S, H * Dh).astype(dt), "dp", None, "tp")
    return out @ p["wo"].astype(dt), new_cache


# ------------------------------------------------------------------------ MLP

def init_mlp(key, cfg, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.gated_mlp:
        p = {"w_gate": dense_init(ks[0], (d, f)),
             "w_in": dense_init(ks[1], (d, f)),
             "w_out": dense_init(ks[2], (f, d), scale=1.0 / np.sqrt(f))}
        s = {"w_gate": ("embed", "mlp"), "w_in": ("embed", "mlp"),
             "w_out": ("mlp", "embed")}
    else:
        p = {"w_in": dense_init(ks[1], (d, f)),
             "w_out": dense_init(ks[2], (f, d), scale=1.0 / np.sqrt(f))}
        s = {"w_in": ("embed", "mlp"), "w_out": ("mlp", "embed")}
    return p, s


def apply_mlp(p, x, cfg):
    dt = x.dtype
    if cfg.gated_mlp:
        h = jax.nn.silu(x @ p["w_gate"].astype(dt)) * (x @ p["w_in"].astype(dt))
    else:
        h = jax.nn.gelu(x @ p["w_in"].astype(dt))
    h = constrain(h, "dp", None, "tp")
    return h @ p["w_out"].astype(dt)
