"""Logical-axis sharding rules -> PartitionSpecs (DESIGN.md §4).

Every param carries a tuple of logical axis names (from the model init).
Resolution is greedy and *divisibility-safe*:

  pass 1 (TP): each logical name tries its preferred mesh axes; an axis is
    taken only if it divides the dim and isn't already used on this param.
    (qwen's 40 heads on a 16-way 'model' axis simply fall through — the
    assignment's sharding footgun, handled by construction.)
  pass 2 (FSDP): remaining axes (pod, data, and 'model' if still free) are
    swept onto the largest divisible dims of large params, fully sharding
    weights ZeRO-3 style.

Optimizer state: Quant8Leaf lives in the flat block domain — codes/absmax/
master shard their block dim over *all* mesh axes (whole quantization blocks
per device); Full32Leaf mirrors the param's spec.  Bit-packed sub-byte codes
(``PackedCodes``, DESIGN.md §9) shard the *block-count* axis (dim 0) exactly
like plain codes — never the byte axis, whose width is a per-block packing
detail — so k-bit states inherit the whole-blocks-per-device guarantee.
The pooled dispatch's ``QuantArena`` (DESIGN.md §10) is that same flat
block domain with every quantized leaf concatenated, and shards
identically (block dim over all axes); pooled masters keep the param spec
and the fp32 small-leaf pool (``Pool32Arena``) is replicated.  Muon's
matrix momentum (DESIGN.md §11) needs no extra rule: it is a one-state
``Quant8Leaf`` riding per-leaf inside the pooled layout, so the block dim
of its codes/absmax shards over all axes like every other quantized state
while the Newton–Schulz matmuls consume the (param-sharded) matrix view.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.analysis import contracts as _contracts
from repro.analysis import mutations as _mutations
from repro.core.lowbit import PackedCodes
from repro.core.optim.base import (Full32Leaf, Pool32Arena, Pool32Leaf,
                                   PooledQuantLeaf, Quant8Leaf, QuantArena)
from repro.core.optim.adafactor import AdafactorLeaf
from repro.errors import ConfigError
from repro.kernels import common as _kernels_common

Pytree = Any

# preferred mesh axes per logical axis name (pass 1)
DEFAULT_TP_RULES = {
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "vocab": ("model",),
    "expert": ("model",),
    "lru": ("model",),
    "head_out": (),
    "embed": (),            # embed dim is FSDP territory, not TP
    "embed_out": (),
    "layers": (),           # scan dim: never sharded
    "unsharded": (),
}


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    tp_rules: Optional[dict] = None
    fsdp_axes: tuple = ("pod", "data")
    fsdp_include_model_if_free: bool = True
    fsdp_min_size: int = 1 << 20       # params smaller than 1M stay replicated
    data_axes: tuple = ("pod", "data")  # batch sharding
    # Params containing these logical dims are left out of the FSDP sweep:
    # a head/embedding that is both vocab-TP and embed-FSDP makes SPMD
    # resolve the head backward by all-gathering f32 logit grads
    # (26 GiB/device measured; EXPERIMENTS.md §Perf C3).
    fsdp_exclude_logical: tuple = ("vocab",)

    def rules(self):
        r = dict(DEFAULT_TP_RULES)
        if self.tp_rules:
            r.update(self.tp_rules)
        return r


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name]


def resolve_spec(logical: tuple, shape: tuple, mesh: Mesh,
                 policy: ShardingPolicy) -> P:
    """Greedy TP + FSDP resolution for one param."""
    rules = policy.rules()
    if len(logical) != len(shape):
        raise ConfigError(f"logical axes {logical} do not match param "
                          f"shape {shape}")
    assign: list[list[str]] = [[] for _ in shape]
    used: set[str] = set()
    avail = set(mesh.axis_names)

    # pass 1: TP preferences
    for i, (name, dim) in enumerate(zip(logical, shape)):
        for ax in rules.get(name, ()):  # unknown names -> no TP
            if ax in avail and ax not in used and dim % _axis_size(mesh, ax) == 0:
                assign[i].append(ax)
                used.add(ax)
                break

    # pass 2: FSDP sweep for large params
    if (int(np.prod(shape)) >= policy.fsdp_min_size
            and not any(l in policy.fsdp_exclude_logical for l in logical)):
        fsdp = list(policy.fsdp_axes)
        if policy.fsdp_include_model_if_free and "model" not in used \
                and "model" in avail:
            fsdp.append("model")
        for ax in fsdp:
            if ax not in avail or ax in used:
                continue
            # place on the largest dim still divisible by the extra factor
            order = sorted(range(len(shape)),
                           key=lambda i: -(shape[i] // max(
                               math.prod(_axis_size(mesh, a) for a in assign[i]), 1)))
            for i in order:
                if logical[i] in ("layers",):
                    continue
                cur = math.prod(_axis_size(mesh, a) for a in assign[i]) if assign[i] else 1
                if shape[i] % (cur * _axis_size(mesh, ax)) == 0:
                    assign[i].append(ax)
                    used.add(ax)
                    break

    return P(*[tuple(a) if len(a) > 1 else (a[0] if a else None)
               for a in assign])


def param_shardings(specs: Pytree, abstract_params: Pytree, mesh: Mesh,
                    policy: ShardingPolicy) -> Pytree:
    """Tree of NamedShardings matching the params tree."""
    def one(spec, p):
        return NamedSharding(mesh, resolve_spec(tuple(spec), tuple(p.shape),
                                                mesh, policy))
    is_spec = lambda t: isinstance(t, tuple) and all(isinstance(e, str) for e in t)
    return jax.tree_util.tree_map(one, specs, abstract_params, is_leaf=is_spec)


def flat_block_spec(mesh: Mesh) -> P:
    """Spec for the flat block domain: block dim over ALL mesh axes."""
    return P(tuple(mesh.axis_names), None)


# ------------------------------------------ partitioned (ZeRO-1) plumbing
# (DESIGN.md §12.)  The partitioned optimizer dispatch splits the pooled
# arenas' leading dim into per-owner spans (core.optim.base.ArenaPartition)
# and runs each span on its owner.  These helpers own the mesh mechanics:
# the owned-span PartitionSpec, the shard_map wrapper that pads the arena
# to the partition's padded domain and runs one local update per device
# (grads reduce-scatter into the span layout on entry; updated master
# slices all-gather at their use sites), and the whole-leaf owner routing
# used for muon matrix leaves.


def owned_span_spec(ndim: int, axes="data") -> P:
    """Spec placing dim 0 (the block/element dim) on the partition
    axes (a name or tuple of names, e.g. ("pod", "data")): each device
    holds exactly its owned span of the padded arena."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    return P(axes, *([None] * (ndim - 1)))


def shard_map_over_spans(mesh: Mesh, axes, part, fn, spans, consts=()):
    """Run ``fn(args, consts)`` with every array in ``spans`` split into
    per-owner spans of ``part`` (an ArenaPartition) along dim 0.

    Arrays are padded from ``part.total`` to ``part.padded_total`` rows
    (trailing owners own padding — their kernels run on zeros, discarded
    on unpad), resharded onto the partition ``axes`` (this is the grads'
    reduce-scatter when they arrive replicated or otherwise sharded), and
    each device calls ``fn`` once on its local ``(span_pad, ...)`` views.
    ``consts`` are replicated operands (codebooks, traced scalars).
    Outputs must be span-shaped arrays; they come back unpadded to
    ``part.total`` rows.
    """
    import jax.numpy as jnp

    pad = part.padded_total - part.total

    def padrows(a):
        a = jnp.asarray(a)
        if pad == 0:
            return a
        return jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))

    arrays = [padrows(a) for a in spans]
    consts = tuple(consts)
    n_arr = len(arrays)

    def inner(*flat):
        return fn(flat[:n_arr], flat[n_arr:])

    in_specs = tuple(owned_span_spec(a.ndim, axes) for a in arrays) \
        + tuple(P() for _ in consts)
    local_args = [jax.ShapeDtypeStruct((part.span_pad,) + a.shape[1:],
                                       a.dtype) for a in arrays]
    # out-spec inference must not perturb the trace-time dispatch counter
    # (opt_fused_dispatches counts real launches only)
    from repro.kernels import ops as _kops
    with _kops.dispatch_count_paused():
        out_shapes = jax.eval_shape(inner, *local_args, *consts)
    out_specs = tuple(owned_span_spec(len(o.shape), axes)
                      for o in out_shapes)
    outs = jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*arrays, *consts)
    return tuple(o[:part.total] for o in outs)


def replicate_for_scales(mesh: Mesh, arrays):
    """Constrain arrays to fully-replicated placement so a following
    global reduction (the LAMB/LARS segment-norm pass) compiles as the
    oracle's single-device reduction on every device — SPMD distributing
    it would change the f32 summation order (DESIGN.md §12)."""
    if _mutations.active("drop_replication_pin"):
        # Seeded violation for the replicated(...) auditor (analysis §15):
        # skip the pin so the partitioned lowering loses its §12 guarantee.
        return tuple(arrays)
    rep = NamedSharding(mesh, P())

    def one(x):
        if x is None:
            return None
        if isinstance(x, PackedCodes):
            return dataclasses.replace(
                x, packed=jax.lax.with_sharding_constraint(x.packed, rep))
        return jax.lax.with_sharding_constraint(x, rep)

    return tuple(one(a) for a in arrays)


def owner_routed(mesh: Mesh, axes, owner: int, fn, args):
    """Whole-leaf owner routing (muon matrix leaves, DESIGN.md §12): only
    the device whose (major-to-minor combined) index along the partition
    ``axes`` equals ``owner`` computes ``fn(*args)``; the result
    broadcasts to the replicas via a psum against zeros.  All result
    leaves round-trip through f32 (exact for uint8 codes and f32 state),
    so the broadcast is bit-exact."""
    import jax.numpy as jnp
    from repro.kernels import ops as _kops

    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    with _kops.dispatch_count_paused():
        out_tree = jax.eval_shape(fn, *args)

    def routed(*a):
        def compute(ops):
            out = fn(*ops)
            return jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float32), out)

        def zeros(ops):
            del ops
            return jax.tree_util.tree_map(
                lambda sd: jnp.zeros(sd.shape, jnp.float32), out_tree)

        idx = jax.lax.axis_index(axes[0])
        for ax in axes[1:]:
            idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
        outf = jax.lax.cond(idx == owner, compute, zeros, a)
        outf = jax.lax.psum(outf, axes)
        return jax.tree_util.tree_map(lambda x, sd: x.astype(sd.dtype),
                                      outf, out_tree)

    return jax.shard_map(routed, mesh=mesh,
                         in_specs=tuple(P() for _ in args),
                         out_specs=P(), check_vma=False)(*args)


def opt_state_shardings(abstract_opt_state, param_shard_tree, mesh: Mesh,
                        policy: ShardingPolicy):
    """Shardings for a Block8bitOptimizer / Adafactor state."""
    blocks = NamedSharding(mesh, flat_block_spec(mesh))
    vec = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    rep = NamedSharding(mesh, P())

    def code_sharding(c):
        # Packed codes: the sharding rides on the packed uint8 child so
        # the tree mirrors the state's structure; dim 0 is still the
        # block-count axis, the byte axis stays unsharded.
        if isinstance(c, PackedCodes):
            return dataclasses.replace(c, packed=blocks)
        return blocks

    def leaf(st, pshard):
        if isinstance(st, Quant8Leaf):
            return Quant8Leaf(master=pshard, codes_m=code_sharding(st.codes_m),
                              absmax_m=vec,
                              codes_r=None if st.codes_r is None
                              else code_sharding(st.codes_r),
                              absmax_r=None if st.absmax_r is None else vec,
                              shape=st.shape, n=st.n)
        if isinstance(st, PooledQuantLeaf):
            # pooled dispatch (DESIGN.md §10): only the param-shaped master
            # lives per leaf; the arena is sharded below.
            return dataclasses.replace(st, master=pshard)
        if isinstance(st, Pool32Leaf):
            return st                      # no arrays; Pool32Arena below
        if isinstance(st, Full32Leaf):
            return Full32Leaf(master=pshard, m=pshard,
                              r=None if st.r is None else pshard)
        if isinstance(st, AdafactorLeaf):
            def reduce_last(ps, drop_axis):
                spec = list(ps.spec) + [None] * (st.master.ndim - len(ps.spec))
                del spec[drop_axis]
                return NamedSharding(mesh, P(*spec))
            return AdafactorLeaf(
                master=pshard, m=pshard,
                v_row=None if st.v_row is None else reduce_last(pshard, -1),
                v_col=None if st.v_col is None else reduce_last(pshard, -2),
                v_full=None if st.v_full is None else pshard)
        raise TypeError(type(st))

    is_state_leaf = lambda x: isinstance(
        x, (Quant8Leaf, Full32Leaf, PooledQuantLeaf, Pool32Leaf,
            AdafactorLeaf))
    leaves = jax.tree_util.tree_map(leaf, abstract_opt_state.leaves,
                                    param_shard_tree, is_leaf=is_state_leaf)
    extra = {}
    if getattr(abstract_opt_state, "gnorm_vec", None) is not None:
        # percentile-clipping gnorm history: tiny, replicated everywhere
        extra["gnorm_vec"] = rep
    arena = getattr(abstract_opt_state, "arena", None)
    if arena is not None:
        # the arena is the flat block domain itself: block dim over ALL
        # mesh axes, exactly like per-leaf codes (total_blocks is a sum of
        # per-leaf shard_multiple-padded counts, so it divides evenly)
        extra["arena"] = QuantArena(
            codes_m=code_sharding(arena.codes_m), absmax_m=vec,
            codes_r=None if arena.codes_r is None
            else code_sharding(arena.codes_r),
            absmax_r=None if arena.absmax_r is None else vec,
            segments=arena.segments,
            partition=getattr(arena, "partition", None))
    pool32 = getattr(abstract_opt_state, "pool32", None)
    if pool32 is not None:
        # pooled small leaves: tiny by construction, replicated like the
        # per-leaf Full32 small leaves they replace
        extra["pool32"] = Pool32Arena(
            master=rep, m=rep, r=None if pool32.r is None else rep,
            segments=pool32.segments,
            partition=getattr(pool32, "partition", None))
    return type(abstract_opt_state)(step=rep, leaves=leaves, **extra)


def batch_sharding(mesh: Mesh, policy: ShardingPolicy, ndim: int = 2,
                   batch_dim_size: Optional[int] = None):
    """Batch-dim sharding over the data axes; drops axes that do not divide
    the batch (long_500k has global_batch=1 -> fully replicated)."""
    axes = tuple(a for a in policy.data_axes if a in mesh.axis_names)
    if batch_dim_size is not None:
        kept = []
        prod = 1
        for a in axes:
            if batch_dim_size % (prod * mesh.shape[a]) == 0:
                kept.append(a)
                prod *= mesh.shape[a]
        axes = tuple(kept)
    if not axes:
        return NamedSharding(mesh, P(*([None] * ndim)))
    return NamedSharding(mesh, P(axes, *([None] * (ndim - 1))))


def cache_shardings(abstract_cache, cfg, mesh: Mesh, policy: ShardingPolicy):
    """KV-cache / recurrent-state shardings for serving.

    batch dim -> data axes.  Attention caches additionally shard kv_heads on
    'model' when divisible, else the *sequence* dim on 'model' (sequence
    parallelism for GQA kv < model axis — DESIGN.md §4).
    """
    dp = tuple(a for a in policy.data_axes if a in mesh.axis_names)
    msize = mesh.shape.get("model", 1)

    def one(x):
        shape = x.shape
        nd = len(shape)
        lead_scan = cfg.scan_layers and cfg.n_superblocks > 0
        spec = [None] * nd
        b_idx = 1 if lead_scan else 0
        if nd > b_idx and shape[b_idx] % max(
                math.prod(mesh.shape[a] for a in dp), 1) == 0:
            spec[b_idx] = dp
        # attention kv cache: (..., B, S, KV, Dh) or absmax (..., B, S, KV)
        if nd - b_idx in (3, 4) and "model" in mesh.axis_names:
            kv_idx = nd - 2 if nd - b_idx == 4 else nd - 1
            s_idx = kv_idx - 1
            if shape[kv_idx] % msize == 0:
                spec[kv_idx] = "model"
            elif shape[s_idx] % msize == 0:
                spec[s_idx] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map(one, abstract_cache)


# ------------------------------------------------- compile contracts (§15)
# replicate_for_scales is a with_sharding_constraint, so dropping it never
# changes numerics on one device — only the *lowering* betrays the loss.
# These contracts pin the §12 guarantee at the StableHLO level.

def _check_replicated_scales(low, cell):
    if getattr(cell, "partition", 1) <= 1:
        return None  # no mesh, no pins to check
    # Count only vector pins and skip the (256,) codebook constants: those
    # are pinned by the arena layout regardless of replicate_for_scales,
    # so a lost scale pin must not hide behind them.
    return _contracts.check_replicated(
        low.text, min_pins=1, vectors_only=True,
        exclude_shapes=((_kernels_common.CODEBOOK_SIZE,),))


def _check_partition_pins(pair, cell):
    """pair:partition — the partitioned lowering must carry the §12
    replication pins its unpartitioned twin has no reason to emit."""
    pins = {k: _contracts.replicated_pins(low.text)
            for k, low in pair.items()}
    on = max(pins.values())
    off = min(pins.values())
    ok = on >= 1 and on > off
    return ok, f"replicated pins per partition setting: {pins}"


_contracts.register(
    "partitioned_step.replicated_scales", "step", _check_replicated_scales,
    doc="partitioned lowering pins tensor scales / gnorm reductions "
        "fully replicated (§12 bit-exactness)")
_contracts.register(
    "partitioned_step.partition_pins", "pair:partition",
    _check_partition_pins,
    doc="turning partitioning on introduces replication pins; turning it "
        "off removes them (the pin is partition-conditional, §12)")
