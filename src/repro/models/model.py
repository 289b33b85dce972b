"""Config-driven decoder LM assembly covering all assigned families.

A model is a stack of blocks cycled from ``cfg.block_pattern``
(attn | rglru | mlstm | slstm).  Layers are grouped into *super-blocks* (one
full pattern cycle) whose params are stacked and iterated with
``jax.lax.scan`` — bounded HLO size for the 512-device dry-run; remainder
layers (n_layers % len(pattern)) are applied unscanned.

Pure functional API:
  init_model(cfg, key)                       -> (params, logical_specs)
  forward(cfg, params, tokens, embeds=None)  -> (logits, metrics)     # train
  prefill(cfg, params, tokens, ...)          -> (logits, cache)
  decode_step(cfg, params, token, cache, pos)-> (logits, cache)
  init_cache(cfg, batch, max_len)            -> cache pytree
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import embedding as emb
from repro.models import layers, moe, recurrent, xlstm
from repro.models.constrain import constrain, constrain_block_params
from repro.telemetry import tracing as _tracing

Pytree = Any


# ------------------------------------------------------------------ blocks

def _init_block(key, cfg, kind: str):
    ks = jax.random.split(key, 4)
    if kind == "attn":
        attn_p, attn_s = layers.init_attention(ks[0], cfg)
        n1p, n1s = layers.init_norm(cfg.d_model, cfg.norm_type)
        p = {"norm1": n1p, "attn": attn_p}
        s = {"norm1": n1s, "attn": attn_s}
        if cfg.is_moe:
            mp, ms = moe.init_moe(ks[1], cfg)
            p["moe"], s["moe"] = mp, ms
        else:
            mp, ms = layers.init_mlp(ks[1], cfg)
            p["mlp"], s["mlp"] = mp, ms
        if not cfg.parallel_block:
            n2p, n2s = layers.init_norm(cfg.d_model, cfg.norm_type)
            p["norm2"], s["norm2"] = n2p, n2s
        return p, s
    if kind == "rglru":
        rp, rs = recurrent.init_rglru_block(ks[0], cfg)
        n1p, n1s = layers.init_norm(cfg.d_model, cfg.norm_type)
        p = {"norm1": n1p, "rec": rp}
        s = {"norm1": n1s, "rec": rs}
        if cfg.d_ff:
            n2p, n2s = layers.init_norm(cfg.d_model, cfg.norm_type)
            mp, ms = layers.init_mlp(ks[1], cfg)
            p.update(norm2=n2p, mlp=mp)
            s.update(norm2=n2s, mlp=ms)
        return p, s
    if kind == "mlstm":
        cp, cs = xlstm.init_mlstm_block(ks[0], cfg)
        n1p, n1s = layers.init_norm(cfg.d_model, cfg.norm_type)
        return {"norm1": n1p, "cell": cp}, {"norm1": n1s, "cell": cs}
    if kind == "slstm":
        cp, cs = xlstm.init_slstm_block(ks[0], cfg)
        n1p, n1s = layers.init_norm(cfg.d_model, cfg.norm_type)
        return {"norm1": n1p, "cell": cp}, {"norm1": n1s, "cell": cs}
    raise ValueError(kind)


def _apply_block(p, x, cfg, kind: str, *, positions, state=None,
                 cache_len=None, paged=None):
    """Returns (x_out, new_state, metrics). ``state``: layer cache for
    decode (attn: {k,v}; recurrent kinds: cell state), or None.  ``paged``:
    layers.PagedContext during paged-KV decode (DESIGN.md §17) — only attn
    blocks consume it; recurrent kinds keep their per-slot dense state."""
    metrics = {}

    def ffn(h):                        # -> (out, metrics)
        with _tracing.annotate("mlp"):
            if "moe" in p:
                return moe.apply_moe(p["moe"], h, cfg)
            return layers.apply_mlp(p["mlp"], h, cfg), {}

    if kind == "attn":
        h = layers.apply_norm(p["norm1"], x, cfg.norm_type)
        with _tracing.annotate("attention"):
            a_out, new_cache = layers.apply_attention(
                p["attn"], h, cfg, positions=positions, cache=state,
                cache_len=cache_len, paged=paged)
        if cfg.parallel_block:
            f_out, metrics = ffn(h)
            x = x + a_out + f_out
        else:
            x = x + a_out
            h2 = layers.apply_norm(p["norm2"], x, cfg.norm_type)
            f_out, metrics = ffn(h2)
            x = x + f_out
        return x, new_cache, metrics
    if kind == "rglru":
        h = layers.apply_norm(p["norm1"], x, cfg.norm_type)
        r_out, new_state = recurrent.apply_rglru_block(p["rec"], h, cfg, state=state)
        x = x + r_out
        if cfg.d_ff:
            h2 = layers.apply_norm(p["norm2"], x, cfg.norm_type)
            x = x + ffn(h2)[0]
        return x, new_state, metrics
    if kind in ("mlstm", "slstm"):
        h = layers.apply_norm(p["norm1"], x, cfg.norm_type)
        fn = xlstm.apply_mlstm_block if kind == "mlstm" else xlstm.apply_slstm_block
        c_out, new_state = fn(p["cell"], h, cfg, state=state)
        return x + c_out, new_state, metrics
    raise ValueError(kind)


# ----------------------------------------------------------- init_model

def init_model(cfg, key) -> tuple[Pytree, Pytree]:
    keys = jax.random.split(key, 6)
    params, specs = {}, {}
    params["embed"], specs["embed"] = emb.init_embedding(keys[0], cfg)
    fp, fs = emb.init_frontend(keys[1], cfg)
    if fp:
        params["frontend"], specs["frontend"] = fp, fs
    pattern = cfg.block_pattern

    def init_super(k):
        ks = jax.random.split(k, len(pattern))
        ps, ss = {}, {}
        for i, kind in enumerate(pattern):
            bp, bs = _init_block(ks[i], cfg, kind)
            ps[f"b{i}_{kind}"] = bp
            ss[f"b{i}_{kind}"] = bs
        return ps, ss

    n_super = cfg.n_superblocks
    if cfg.scan_layers and n_super > 0:
        sk = jax.random.split(keys[2], n_super)
        stacked = jax.vmap(lambda k: init_super(k)[0])(sk)
        _, sspec = init_super(keys[2])
        # prepend the scan ("layers") logical axis to every spec tuple
        sspec = jax.tree_util.tree_map(
            lambda t: ("layers",) + t, sspec,
            is_leaf=lambda t: isinstance(t, tuple) and all(isinstance(e, str) for e in t))
        params["blocks"], specs["blocks"] = stacked, sspec
    elif n_super > 0:
        blocks, bspecs = [], []
        sk = jax.random.split(keys[2], n_super)
        for i in range(n_super):
            bp, bs = init_super(sk[i])
            blocks.append(bp)
            bspecs.append(bs)
        params["blocks_list"], specs["blocks_list"] = blocks, bspecs
    rem = cfg.n_remainder_layers
    if rem:
        rk = jax.random.split(keys[3], rem)
        rp, rs = [], []
        for i in range(rem):
            kind = pattern[i % len(pattern)]
            bp, bs = _init_block(rk[i], cfg, kind)
            rp.append({f"{kind}": bp})
            rs.append({f"{kind}": bs})
        params["rem_blocks"], specs["rem_blocks"] = rp, rs
    nf, nfs = layers.init_norm(cfg.d_model, cfg.norm_type)
    params["final_norm"], specs["final_norm"] = nf, nfs
    hp, hs = emb.init_head(keys[4], cfg)
    if hp:
        params["head"], specs["head"] = hp, hs
    return params, specs


# ------------------------------------------------------------- forward

def _remat_wrap(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _superblock_fwd(bp, x, cfg, positions, states=None, cache_len=None,
                    paged=None):
    """Apply one super-block. states: dict keyed like bp or None."""
    bp = constrain_block_params(bp)
    new_states, metrics_acc = {}, []
    for i, kind in enumerate(cfg.block_pattern):
        name = f"b{i}_{kind}"
        st = states[name] if states is not None else None
        # Sequence parallelism: the residual stream between TP regions is
        # sharded on (batch->dp, seq->tp).  Norm/residual work shrinks by
        # tp_size and the Megatron f32 dL/dx all-reduces become bf16
        # gathers/reduce-scatters (EXPERIMENTS.md §Perf C4).
        x = constrain(x, "dp", "tp", None)
        x, ns, mt = _apply_block(bp[name], x, cfg, kind, positions=positions,
                                 state=st, cache_len=cache_len, paged=paged)
        new_states[name] = ns
        if mt:
            metrics_acc.append(mt)
    agg = {}
    if metrics_acc:
        for k in metrics_acc[0]:
            agg[k] = jnp.mean(jnp.stack([m[k] for m in metrics_acc]))
    return x, new_states, agg


def _run_blocks(params, x, cfg, positions, caches=None, cache_len=None,
                paged=None):
    """Run all layers. caches: None (no state io) or pytree with leading
    n_super dim for the scanned part + list for remainder.  ``paged`` (a
    layers.PagedContext) rides into the scan body as a loop constant — the
    page table and per-slot positions are layer-invariant."""
    metrics = {}
    decode_mode = caches is not None

    if cfg.scan_layers and cfg.n_superblocks > 0:
        if decode_mode:
            def body(h, xs):
                bp, st = xs
                h, ns, mt = _superblock_fwd(bp, h, cfg, positions, st,
                                            cache_len, paged)
                return h, (ns, mt)
            x, (new_scan_cache, mts) = jax.lax.scan(
                body, x, (params["blocks"], caches["scan"]))
        else:
            def body(h, bp):
                h, _, mt = _superblock_fwd(bp, h, cfg, positions, None, None)
                return h, mt
            body = _remat_wrap(body, cfg)
            x, mts = jax.lax.scan(body, x, params["blocks"])
            new_scan_cache = None
        if mts:
            metrics = {k: jnp.mean(v) for k, v in mts.items()}
    elif "blocks_list" in params:
        new_scan_cache = []
        for i, bp in enumerate(params["blocks_list"]):
            st = caches["scan"][i] if decode_mode else None
            x, ns, mt = _superblock_fwd(bp, x, cfg, positions, st, cache_len,
                                        paged)
            new_scan_cache.append(ns)
            metrics.update(mt)
        if not decode_mode:
            new_scan_cache = None
    else:
        new_scan_cache = None

    new_rem = []
    if "rem_blocks" in params:
        for i, bp in enumerate(params["rem_blocks"]):
            kind = cfg.block_pattern[i % len(cfg.block_pattern)]
            st = caches["rem"][i] if decode_mode else None
            x, ns, mt = _apply_block(bp[kind], x, cfg, kind,
                                     positions=positions, state=st,
                                     cache_len=cache_len, paged=paged)
            new_rem.append(ns)
            metrics.update(mt)

    new_caches = {"scan": new_scan_cache, "rem": new_rem} if decode_mode else None
    return x, new_caches, metrics


def forward(cfg, params, tokens, embeds=None):
    """Training/eval forward. tokens: (B, S_tok) int32; embeds: optional
    (B, frontend_tokens, d) stub features. Returns (logits (B,S,V), metrics)."""
    x = emb.apply_embedding(params["embed"], tokens, cfg)
    if embeds is not None and "frontend" in params:
        fx = emb.apply_frontend(params["frontend"], embeds, cfg)
        x = jnp.concatenate([fx.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    x, _, metrics = _run_blocks(params, x, cfg, positions)
    x = layers.apply_norm(params["final_norm"], x, cfg.norm_type)
    logits = emb.apply_head(params.get("head", {}), x, params["embed"], cfg)
    return logits, metrics


# --------------------------------------------------------------- serving

def _init_layer_cache(cfg, kind, batch, max_len, n_super=None):
    KV, Dh = cfg.n_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.compute_dtype)
    lead = (n_super,) if n_super else ()
    if kind == "attn":
        eff = min(max_len, cfg.window) if cfg.attn_type == "swa" and cfg.window else max_len
        # SWA caches are ring buffers of size window (long_500k: bounded
        # cache is the point).
        if cfg.kv_cache_bits == 8:
            # beyond-paper: block-wise int8 KV cache (layers.kv_quantize)
            return {"k_codes": jnp.zeros(lead + (batch, eff, KV, Dh), jnp.uint8),
                    "k_absmax": jnp.zeros(lead + (batch, eff, KV), jnp.float32),
                    "v_codes": jnp.zeros(lead + (batch, eff, KV, Dh), jnp.uint8),
                    "v_absmax": jnp.zeros(lead + (batch, eff, KV), jnp.float32)}
        return {"k": jnp.zeros(lead + (batch, eff, KV, Dh), dt),
                "v": jnp.zeros(lead + (batch, eff, KV, Dh), dt)}
    if kind == "rglru":
        W = cfg.lru_width or cfg.d_model
        return {"h": jnp.zeros(lead + (batch, W), jnp.float32),
                "conv": jnp.zeros(lead + (batch, cfg.conv_width - 1, W), jnp.float32)}
    if kind == "mlstm":
        H = cfg.n_heads
        D = int(cfg.d_model * cfg.mlstm_proj_factor) // H
        return (jnp.zeros(lead + (batch, H, D, D), jnp.float32),
                jnp.zeros(lead + (batch, H, D), jnp.float32),
                jnp.zeros(lead + (batch, H), jnp.float32))
    if kind == "slstm":
        d = cfg.d_model
        z = jnp.zeros(lead + (batch, d), jnp.float32)
        return (z, z, z, jnp.full(lead + (batch, d), -jnp.inf, jnp.float32))
    raise ValueError(kind)


def init_cache(cfg, batch, max_len):
    if cfg.scan_layers and cfg.n_superblocks > 0:
        scan_cache = {
            f"b{i}_{kind}": _init_layer_cache(cfg, kind, batch, max_len,
                                              n_super=cfg.n_superblocks)
            for i, kind in enumerate(cfg.block_pattern)}
    else:
        scan_cache = [
            {f"b{i}_{kind}": _init_layer_cache(cfg, kind, batch, max_len)
             for i, kind in enumerate(cfg.block_pattern)}
            for _ in range(cfg.n_superblocks)]
    rem = [
        _init_layer_cache(cfg, cfg.block_pattern[i % len(cfg.block_pattern)],
                          batch, max_len)
        for i in range(cfg.n_remainder_layers)]
    return {"scan": scan_cache, "rem": rem}


def decode_step(cfg, params, token, caches, pos):
    """token: (B, 1) int32; pos: scalar int32 — 0-based index of this token.
    Returns (logits (B, 1, V), new_caches)."""
    x = emb.apply_embedding(params["embed"], token, cfg)
    positions = jnp.full((1, 1), pos, jnp.int32)
    x, new_caches, _ = _run_blocks(params, x, cfg, positions, caches=caches,
                                   cache_len=pos + 1)
    x = layers.apply_norm(params["final_norm"], x, cfg.norm_type)
    logits = emb.apply_head(params.get("head", {}), x, params["embed"], cfg)
    return logits, new_caches


# ------------------------------------------------ paged serving (§17)

def _init_paged_layer_cache(cfg, kind, n_slots, n_pages, page_size, kv_bits,
                            n_super=None):
    """Layer cache for the paged serving path: attn layers share one
    quantized page pool (no batch dim — the page table maps slots to
    pages); recurrent kinds keep per-slot dense state exactly as the
    contiguous cache does."""
    from repro.kernels import paged_kv
    lead = (n_super,) if n_super else ()
    if kind == "attn":
        KV, Dh = cfg.n_kv_heads, cfg.head_dim
        W = paged_kv.packed_row_width(Dh, kv_bits)
        return {"k_codes": jnp.zeros(lead + (n_pages, page_size, KV, W),
                                     jnp.uint8),
                "k_absmax": jnp.zeros(lead + (n_pages, page_size, KV),
                                      jnp.float32),
                "v_codes": jnp.zeros(lead + (n_pages, page_size, KV, W),
                                     jnp.uint8),
                "v_absmax": jnp.zeros(lead + (n_pages, page_size, KV),
                                      jnp.float32)}
    return _init_layer_cache(cfg, kind, n_slots, page_size, n_super=n_super)


def init_paged_cache(cfg, n_slots, n_pages, page_size, kv_bits=8):
    """Paged serving cache pytree (same {"scan","rem"} structure as
    ``init_cache``): per attn layer a pool of ``n_pages`` pages of
    ``page_size`` positions, block-wise quantized to ``kv_bits`` (8-bit
    plain / 4-bit packed codes, DESIGN.md §17)."""
    if cfg.scan_layers and cfg.n_superblocks > 0:
        scan_cache = {
            f"b{i}_{kind}": _init_paged_layer_cache(
                cfg, kind, n_slots, n_pages, page_size, kv_bits,
                n_super=cfg.n_superblocks)
            for i, kind in enumerate(cfg.block_pattern)}
    else:
        scan_cache = [
            {f"b{i}_{kind}": _init_paged_layer_cache(
                cfg, kind, n_slots, n_pages, page_size, kv_bits)
             for i, kind in enumerate(cfg.block_pattern)}
            for _ in range(cfg.n_superblocks)]
    rem = [
        _init_paged_layer_cache(
            cfg, cfg.block_pattern[i % len(cfg.block_pattern)],
            n_slots, n_pages, page_size, kv_bits)
        for i in range(cfg.n_remainder_layers)]
    return {"scan": scan_cache, "rem": rem}


def paged_decode_step(cfg, params, token, caches, paged):
    """One continuous-batching decode step over every slot.

    token: (n_slots, 1) int32 (the last sampled token per slot; inactive
    slots carry a dummy).  ``paged``: layers.PagedContext with per-slot
    positions and the page table.  Returns (logits (n_slots, 1, V),
    new_caches) — pages are appended in place (donate ``caches`` when
    jitting; the serve contract audits this, DESIGN.md §17).
    """
    x = emb.apply_embedding(params["embed"], token, cfg)
    positions = jnp.maximum(paged.positions, 0)[:, None]     # (B, 1)
    x, new_caches, _ = _run_blocks(params, x, cfg, positions, caches=caches,
                                   cache_len=None, paged=paged)
    x = layers.apply_norm(params["final_norm"], x, cfg.norm_type)
    logits = emb.apply_head(params.get("head", {}), x, params["embed"], cfg)
    return logits, new_caches


def _commit_attn_pages(cfg, paged_layer, dense_layer, table_row,
                       prompt_len, kv_bits, lead):
    """Quantize a batch-1 dense prefill cache's k/v rows into the slot's
    allocated pages.  SWA dense caches are rings holding only the last
    ``eff`` positions; exactly those rows are committed (older positions
    are outside every future window, their pages stay zero and masked)."""
    from repro.kernels import paged_kv
    if "k" not in dense_layer:
        raise ValueError("paged commit needs a 16-bit dense prefill cache "
                         "(cfg.kv_cache_bits == 16 for the prefill config)")
    page = paged_layer["k_codes"].shape[2 if lead else 1]
    eff = dense_layer["k"].shape[2 if lead else 1]
    pos = np.arange(prompt_len - min(prompt_len, eff), prompt_len)
    ring_idx = jnp.asarray(pos % eff)
    pids = table_row[jnp.asarray(pos // page)]
    offs = jnp.asarray(pos % page)
    out = dict(paged_layer)
    for name in ("k", "v"):
        dense = dense_layer[name]
        rows = dense[:, 0][:, ring_idx] if lead else dense[0][ring_idx]
        codes, absmax = paged_kv.quantize_rows(rows, kv_bits)
        if lead:
            out[f"{name}_codes"] = paged_layer[f"{name}_codes"].at[
                :, pids, offs].set(codes)
            out[f"{name}_absmax"] = paged_layer[f"{name}_absmax"].at[
                :, pids, offs].set(absmax)
        else:
            out[f"{name}_codes"] = paged_layer[f"{name}_codes"].at[
                pids, offs].set(codes)
            out[f"{name}_absmax"] = paged_layer[f"{name}_absmax"].at[
                pids, offs].set(absmax)
    return out


def commit_prefill_to_paged(cfg, paged_caches, dense_caches, slot,
                            table_row, prompt_len, kv_bits=8):
    """Admit one prefetched request into the paged cache (DESIGN.md §17).

    ``dense_caches`` is a batch-1 ``prefill`` cache built with a 16-bit
    contiguous config (max_len == prompt_len); its attn k/v rows are
    quantized into the pages named by ``table_row`` ((max_pages_per_seq,)
    int32) with the SAME row quantizer the decode append uses, and every
    recurrent layer's state is inserted at batch row ``slot``.  Returns the
    updated paged cache pytree (donate ``paged_caches`` when jitting).
    """
    def insert_slot(pg, dn, lead):
        if lead:
            return pg.at[:, slot].set(dn[:, 0].astype(pg.dtype))
        return pg.at[slot].set(dn[0].astype(pg.dtype))

    def commit_layer(kind, pg_layer, dn_layer, lead):
        if kind == "attn":
            return _commit_attn_pages(cfg, pg_layer, dn_layer, table_row,
                                      prompt_len, kv_bits, lead)
        return jax.tree_util.tree_map(
            lambda pg, dn: insert_slot(pg, dn, lead), pg_layer, dn_layer)

    out = {"rem": [], "scan": None}
    if cfg.scan_layers and cfg.n_superblocks > 0:
        out["scan"] = {
            name: commit_layer(name.split("_", 1)[1], paged_caches["scan"][name],
                               dense_caches["scan"][name], True)
            for name in paged_caches["scan"]}
    else:
        out["scan"] = [
            {name: commit_layer(name.split("_", 1)[1], sb[name],
                                dense_caches["scan"][i][name], False)
             for name in sb}
            for i, sb in enumerate(paged_caches["scan"])]
    for i, layer in enumerate(paged_caches["rem"]):
        kind = cfg.block_pattern[i % len(cfg.block_pattern)]
        out["rem"].append(commit_layer(kind, layer,
                                       dense_caches["rem"][i], False))
    return out


def prefill(cfg, params, tokens, max_len, embeds=None):
    """Run the full prompt, return (logits, caches ready for decode at
    pos=len(prompt))."""
    x = emb.apply_embedding(params["embed"], tokens, cfg)
    if embeds is not None and "frontend" in params:
        fx = emb.apply_frontend(params["frontend"], embeds, cfg)
        x = jnp.concatenate([fx.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    caches = init_cache(cfg, B, max_len)
    x, new_caches, _ = _run_blocks(params, x, cfg, positions, caches=caches,
                                   cache_len=S)
    x = layers.apply_norm(params["final_norm"], x, cfg.norm_type)
    logits = emb.apply_head(params.get("head", {}), x, params["embed"], cfg)
    return logits, new_caches
