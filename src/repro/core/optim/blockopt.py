"""The paper's 8-bit optimizers (and their 32-bit twins) as one engine.

``Block8bitOptimizer`` implements Adam/AdamW/Momentum/LAMB/LARS/AdaGrad with
per-leaf state that is either block-wise 8-bit quantized (``Quant8Leaf``) or
full 32-bit (``Full32Leaf`` — used for the 32-bit baselines, for leaves below
``min_8bit_size``, and for leaves matched by the stable-embedding override,
paper §2.3).

The update is the paper's §2 procedure: dequantize -> 32-bit math ->
requantize, executed through the ``(algo, impl)`` registry behind
``repro.kernels.ops.fused_update``: one fused Pallas pass per state tensor
on TPU (``impl='pallas'``), the same kernels interpreted on CPU
(``impl='interpret'``), or the parameterized jnp oracle (``impl='jnp'``).
Every algorithm and every ablation mode (stochastic rounding, tensor-wise
quantization) takes this one path — there is no separate multi-pass
fallback anymore (DESIGN.md §3).

State signedness per algorithm (paper §2.2: the strictly-positive second
moment uses the unsigned dynamic map with the sign bit re-purposed as an
extra fraction bit):

  adam/adamw/lamb : m -> signed dynamic, r -> unsigned dynamic
  momentum/lars   : m -> signed dynamic
  adagrad         : accumulator -> unsigned dynamic (stored in the m slot)

Storage bitwidth is per state slot (``cfg.state_bits``; DESIGN.md §9): each
slot gets a :class:`~repro.core.lowbit.CodeFormat` whose 2^bits-entry
codebook and (for sub-byte widths) bit-packed ``PackedCodes`` container
flow through the same fused kernels — e.g. ``state_bits=(4, 8)`` stores a
4-bit first moment next to an 8-bit second moment (Li et al. 2023).

Optional percentile clipping (``cfg.percentile_clipping < 100``) maintains a
per-optimizer history of squared global gradient norms in
``OptState.gnorm_vec`` (bitsandbytes-style; DESIGN.md §7) and scales
gradients by a scalar inside the fused kernel — no extra pass over the
states.  The history is ordinary optimizer state: it is checkpointed and
restored like every other leaf.

**Pooled single dispatch** (``cfg.pooled``, default on; DESIGN.md §10):
``init`` concatenates every quantized leaf's statistics into one
:class:`~repro.core.optim.base.QuantArena` and every sub-``min_quant_size``
leaf's fp32 state into one :class:`~repro.core.optim.base.Pool32Arena`, so
``apply`` issues **one** ``kops.fused_update`` per arena (plus one jnp
update for the fp32 pool) instead of one launch per parameter leaf.
Per-leaf stochastic-rounding seeds become per-block seed vectors and
LAMB/LARS trust ratios are finalized per arena *segment*, so pooled and
per-leaf dispatch are bit-identical — ``pooled=False`` is kept as the
parity oracle (and serves the tensor-wise ablation, which needs a
per-tensor absmax).  Checkpoints always store the per-leaf canonical form
(:func:`unpool_state`), so pooled and per-leaf runs share checkpoints in
both directions.

**Matrix-class leaves** (DESIGN.md §11): subclasses can route leaves to a
matrix algorithm via the ``_leaf_class``/``_init_matrix_leaf`` hooks and
re-point ``self._ew_algo`` at their element-wise fallback —
``MuonOptimizer`` routes 2-D leaves to Newton–Schulz momentum updates
(one-state ``Quant8Leaf``, dispatched per leaf even under pooling) while
everything else runs fused adamw through the machinery above.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lowbit import CodeFormat, PackedCodes
from repro.core.lowbit import unwrap_codes as lowbit_unwrap
from repro.core.optim import base
from repro.core.optim.base import (ArenaPartition, FlatSegment, Full32Leaf,
                                   OptimConfig, Pool32Arena, Pool32Leaf,
                                   PooledQuantLeaf, Quant8Leaf, QuantArena,
                                   QuantSegment, blocks_to_param,
                                   flatten_to_blocks, make_buckets,
                                   make_partition, path_str)
from repro.errors import ConfigError, FormatError
from repro.models.constrain import constrain as _constrain
from repro.telemetry import tracing as _tracing
from repro.kernels import fused_update as kfu
from repro.kernels import ops as kops

Pytree = Any


class OptState(NamedTuple):
    step: jax.Array           # int32 scalar, number of updates applied
    # tree of Quant8Leaf / Full32Leaf (per-leaf dispatch) or
    # PooledQuantLeaf / Pool32Leaf / Full32Leaf (pooled dispatch)
    leaves: Pytree
    # (pclip_history,) f32 squared-gnorm history, or None when percentile
    # clipping is off (cfg.percentile_clipping == 100).
    gnorm_vec: Optional[jax.Array] = None
    # Pooled-dispatch arenas (DESIGN.md §10); None on the per-leaf layout.
    arena: Optional[QuantArena] = None
    pool32: Optional[Pool32Arena] = None


def _is_state_leaf(x) -> bool:
    return isinstance(x, (Quant8Leaf, Full32Leaf, PooledQuantLeaf,
                          Pool32Leaf))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GradBuffer:
    """ZeRO-2 accumulated-gradient buffer (DESIGN.md §13).

    ``blocks`` holds the gradients of every pooled quantized leaf in the
    arena's flat block domain — the same layout the fused update consumes —
    padded to the partition's ``padded_total`` rows and, on a partition
    mesh, sharded to the owned span: the replicated param-shaped grad
    pytree never materializes.  ``ride`` carries the leaves that don't
    live in the arena (Full32 overrides, muon matrix leaves, pooled small
    leaves) as param-shaped f32 grads in flatten order.  ``layout`` is the
    static per-leaf routing table, one entry per param leaf in flatten
    order::

        ("arena", block_offset, n_blocks, shape, n)   |   ("ride", pos, shape)

    ``part`` is the arena's static ownership map (None when the arena is
    unpartitioned or absent) — ``accumulate_grads`` needs it to slice the
    per-bucket adds."""
    blocks: Optional[jax.Array]     # (padded_total, B) f32 | None
    ride: tuple                     # param-shaped f32 grads
    layout: tuple                   # static routing table
    part: Optional[ArenaPartition] = None

    def tree_flatten(self):
        return ((self.blocks, self.ride), (self.layout, self.part))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], tuple(children[1]), *aux)


def _state1_signed(algo: str) -> bool:
    return algo != "adagrad"


class Block8bitOptimizer:
    """init/apply optimizer owning the f32 master copy of the params."""

    def __init__(self, config: OptimConfig,
                 override_32bit: Optional[Callable[[str], bool]] = None,
                 mesh: Optional[Any] = None):
        self.cfg = config
        self.override_32bit = override_32bit or (lambda path: False)
        # Mesh for the partitioned (ZeRO-1) dispatch's shard_map path
        # (DESIGN.md §12).  None => the statically-unrolled span dispatch,
        # which computes identical results on any device count.
        self._mesh = mesh
        # The algorithm element-wise leaves run through the fused registry.
        # Matrix-class optimizers (MuonOptimizer, DESIGN.md §11) override
        # `_elementwise_algo` to their fallback algorithm ("adamw") while
        # routing 2-D leaves to the matrix update — the per-leaf routing
        # split.  The base engine has no matrix routing and rejects
        # matrix-class algos outright (feeding the flat block arena into
        # Newton–Schulz would silently orthogonalize garbage).
        self._ew_algo = self._elementwise_algo(config.algo)
        signed1 = _state1_signed(config.algo)
        bits1, bits2 = config.state_bits_pair
        self._fmt1 = CodeFormat(
            bits=bits1, signed=signed1,
            qmap_name=config.qmap_m if signed1 else config.qmap_r)
        self._fmt2 = CodeFormat(bits=bits2, signed=False,
                                qmap_name=config.qmap_r)
        self._qmap1 = jnp.asarray(self._fmt1.codebook())
        self._qmap2 = jnp.asarray(self._fmt2.codebook())
        self._impl = config.impl or kops.default_impl()

    # ------------------------------------------------------------------ init
    def _leaf_is_quantized(self, path: str, param: jax.Array) -> bool:
        if self.cfg.bits == 32:
            return False
        if param.size < self.cfg.min_quant_size:
            return False
        return not self.override_32bit(path)

    def _elementwise_algo(self, algo: str) -> str:
        """The algorithm non-matrix leaves dispatch through the fused
        registry.  Matrix optimizers override this (muon -> "adamw")."""
        if kfu.ALGO_SPECS[algo].matrix:
            raise ValueError(
                f"'{algo}' is a matrix-class algorithm; construct it via "
                f"make_optimizer / MuonOptimizer (DESIGN.md §11) — "
                f"Block8bitOptimizer has no matrix-leaf routing")
        return algo

    def _leaf_class(self, path: str, param: jax.Array) -> str:
        """Per-leaf algorithm class: "ew" (element-wise, the fused-registry
        path) or "matrix" (Newton–Schulz leaves, MuonOptimizer only —
        DESIGN.md §11).  The base engine is entirely element-wise."""
        del path, param
        return "ew"

    def _init_matrix_leaf(self, path: str, param: jax.Array):
        raise NotImplementedError(
            "matrix-class leaves need a matrix optimizer (MuonOptimizer)")

    def init(self, params: Pytree) -> OptState:
        cfg = self.cfg
        if cfg.pooling_active:
            return self._init_pooled(params)

        def init_leaf(path, p):
            path = path_str(path)
            if self._leaf_class(path, p) == "matrix":
                return self._init_matrix_leaf(path, p)
            if self._leaf_is_quantized(path, p):
                # master stays in PARAM SHAPE (sharded like the param) so the
                # fwd/bwd sees per-layer gathers inside the scan; only the
                # quantized statistics live in the flat block domain.  (The
                # flat-master variant all-gathered the whole tensor per step:
                # EXPERIMENTS.md §Perf iteration A2.)
                master = p.astype(jnp.dtype(cfg.master_dtype))
                nb = base.n_blocks_for(p.shape, cfg.block_size,
                                       cfg.shard_multiple)
                bs = cfg.block_size
                second = cfg.has_second_moment
                return Quant8Leaf(
                    master=master,
                    codes_m=self._fmt1.init_codes(nb, bs),
                    absmax_m=jnp.zeros((nb,), jnp.float32),
                    codes_r=self._fmt2.init_codes(nb, bs) if second else None,
                    absmax_r=jnp.zeros((nb,), jnp.float32) if second else None,
                    shape=tuple(p.shape), n=int(p.size))
            master = p.astype(jnp.float32)
            return Full32Leaf(
                master=master,
                m=jnp.zeros_like(master),
                r=jnp.zeros_like(master) if cfg.has_second_moment else None)

        leaves = jax.tree_util.tree_map_with_path(init_leaf, params)
        gnorm_vec = (jnp.zeros((cfg.pclip_history,), jnp.float32)
                     if cfg.percentile_clipping < 100 else None)
        return OptState(step=jnp.zeros((), jnp.int32), leaves=leaves,
                        gnorm_vec=gnorm_vec)

    def _init_pooled(self, params: Pytree) -> OptState:
        """Pooled arena layout (DESIGN.md §10): quantized statistics of all
        quantized leaves concatenate into one QuantArena; small leaves pool
        their fp32 state into one Pool32Arena; masters stay per-leaf in
        param shape (sharded like the param, §Perf A2).  Segment offsets
        are assigned in leaf flatten order, the order ``apply`` re-walks."""
        cfg = self.cfg
        mdt = jnp.dtype(cfg.master_dtype)
        bs = cfg.block_size
        second = cfg.has_second_moment
        qsegs: list = []
        fsegs: list = []
        flat32: list = []
        matrix_paths: list = []

        def init_leaf(path, p):
            path = path_str(path)
            if self._leaf_class(path, p) == "matrix":
                # Matrix-class leaves (muon) never pool: each one is its
                # own Newton–Schulz problem and dispatches per leaf
                # (DESIGN.md §11) — they ride along like Full32 overrides.
                leaf = self._init_matrix_leaf(path, p)
                if isinstance(leaf, Quant8Leaf):
                    # quantized matrix leaves get a whole-leaf owner under
                    # the partitioned dispatch (DESIGN.md §12)
                    matrix_paths.append(path)
                return leaf
            if self._leaf_is_quantized(path, p):
                nb = base.n_blocks_for(p.shape, bs, cfg.shard_multiple)
                off = qsegs[-1].offset + qsegs[-1].n_blocks if qsegs else 0
                qsegs.append(QuantSegment(path, off, nb, tuple(p.shape),
                                          int(p.size)))
                return PooledQuantLeaf(master=p.astype(mdt),
                                       shape=tuple(p.shape), n=int(p.size),
                                       offset=off, n_blocks=nb)
            if p.size < cfg.min_quant_size and not self.override_32bit(path):
                off = fsegs[-1].offset + fsegs[-1].n if fsegs else 0
                fsegs.append(FlatSegment(path, off, int(p.size),
                                         tuple(p.shape)))
                flat32.append(p.reshape(-1).astype(jnp.float32))
                return Pool32Leaf(shape=tuple(p.shape), n=int(p.size),
                                  offset=off)
            # stable-embedding override (paper §2.3): stays a per-leaf
            # Full32Leaf — large, sharded like its param.
            master = p.astype(jnp.float32)
            return Full32Leaf(
                master=master, m=jnp.zeros_like(master),
                r=jnp.zeros_like(master) if second else None)

        leaves = jax.tree_util.tree_map_with_path(init_leaf, params)
        shards = cfg.partition_shards if cfg.partition_active else 0
        mowners = tuple((p, k % max(shards, 1))
                        for k, p in enumerate(matrix_paths))
        arena = None
        if qsegs:
            total = qsegs[-1].offset + qsegs[-1].n_blocks
            arena = QuantArena(
                codes_m=self._fmt1.init_codes(total, bs),
                absmax_m=jnp.zeros((total,), jnp.float32),
                codes_r=self._fmt2.init_codes(total, bs) if second else None,
                absmax_r=jnp.zeros((total,), jnp.float32) if second else None,
                segments=tuple(qsegs),
                # ZeRO-1 ownership over the block dim (DESIGN.md §12):
                # spans are whole quantization blocks aligned to the
                # shard grid, so owned spans match the storage shards
                # (the kernel entry pads each span to its tile rows).
                partition=None if not shards else make_partition(
                    total, shards, grid=max(cfg.shard_multiple, 1),
                    matrix_owners=mowners))
        pool32 = None
        if fsegs:
            total = fsegs[-1].offset + fsegs[-1].n
            master = (jnp.concatenate(flat32) if len(flat32) > 1
                      else flat32[0])
            pool32 = Pool32Arena(
                master=master, m=jnp.zeros((total,), jnp.float32),
                r=jnp.zeros((total,), jnp.float32) if second else None,
                segments=tuple(fsegs),
                # element-granular ownership, lane-aligned (128) spans
                partition=None if not shards else make_partition(
                    total, shards, grid=128))
        gnorm_vec = (jnp.zeros((cfg.pclip_history,), jnp.float32)
                     if cfg.percentile_clipping < 100 else None)
        return OptState(step=jnp.zeros((), jnp.int32), leaves=leaves,
                        gnorm_vec=gnorm_vec, arena=arena, pool32=pool32)

    # ------------------------------------------------------------- algorithms
    def _math32(self, g, p, m, r, lr, step_f):
        """32-bit update math for Full32 leaves — the same parameterized
        update the fused kernels run (kernels/fused_update.update_math),
        with per-tensor norms computed inline.  Returns (m', r', p')."""
        cfg = self.cfg
        spec = kfu.ALGO_SPECS[self._ew_algo]
        s = kfu.update_scalars(lr=lr, beta1=cfg.beta1, beta2=cfg.beta2,
                               eps=cfg.eps, weight_decay=cfg.weight_decay,
                               step=step_f)
        s["tensor_scale"] = kfu.tensor_scale_for(spec, g, p, m, r, s,
                                                 cfg.trust_coeff)
        return kfu.update_math(spec, g, p, m, r, s)

    # -------------------------------------------------------------- clipping
    def percentile_clip(self, grads: Pytree, state: OptState):
        """Percentile-clipping scale for this step (DESIGN.md §7).

        Returns ``(gnorm_scale, new_gnorm_vec)``: the scalar every gradient
        is multiplied by inside the fused kernel, and the updated squared-
        gnorm history.  No-op (scale 1, vec unchanged) when disabled.  The
        history (including the current step's norm) must fill before
        clipping engages, so the first ``pclip_history - 1`` steps are
        never clipped; a spike on the step that fills it can be.

        ``grads`` may be the param-shaped pytree or a ZeRO-2
        :class:`GradBuffer` — the buffer path reduces each leaf on a view
        reshaped back to its param shape, in the same flatten order, so
        the history is bit-identical either way (DESIGN.md §13)."""
        cfg = self.cfg
        if cfg.percentile_clipping >= 100 or state.gnorm_vec is None:
            return jnp.float32(1.0), state.gnorm_vec
        mesh = (self._partition_mesh(cfg.partition_shards)
                if cfg.partition_active else None)
        gn2 = jnp.zeros((), jnp.float32)
        for leaf in self._grad_views(grads):
            if mesh is not None:
                # Partitioned dispatch (DESIGN.md §12): pin the global
                # gnorm reduction to replicated compute so its f32
                # summation order matches the unpartitioned oracle —
                # SPMD would otherwise distribute it (ULP drift in the
                # clip history).
                from repro.sharding import rules as _rules
                (leaf,) = _rules.replicate_for_scales(mesh, (leaf,))
            gn2 = gn2 + jnp.sum(jnp.square(leaf.astype(jnp.float32)))
        hist = state.gnorm_vec
        new_vec = hist.at[jnp.mod(state.step, hist.shape[0])].set(gn2)
        clip2 = jnp.percentile(new_vec, cfg.percentile_clipping)
        warm = (state.step + 1) >= hist.shape[0]
        scale = jnp.where(
            warm & (gn2 > clip2),
            jnp.sqrt(jnp.maximum(clip2, 0.0) / jnp.maximum(gn2, 1e-30)), 1.0)
        return scale.astype(jnp.float32), new_vec

    # ------------------------------------------- ZeRO-2 grad buffer (§13)
    def _grad_layout(self, state: OptState) -> tuple:
        """Static GradBuffer routing table from a (possibly abstract)
        pooled state: one entry per param leaf, flatten order."""
        entries: list = []
        pos = [0]

        def walk(leaf):
            if isinstance(leaf, PooledQuantLeaf):
                entries.append(("arena", leaf.offset, leaf.n_blocks,
                                tuple(leaf.shape), leaf.n))
            else:
                shape = (tuple(leaf.master.shape)
                         if isinstance(leaf, Full32Leaf)
                         else tuple(leaf.shape))
                entries.append(("ride", pos[0], shape))
                pos[0] += 1
            return leaf

        jax.tree_util.tree_map(walk, state.leaves, is_leaf=_is_state_leaf)
        return tuple(entries)

    def _constrain_buffer(self, blocks):
        """Pin the grad buffer to the owned-span layout — the resharding
        onto this constraint IS the per-bucket reduce-scatter when grads
        arrive replicated or param-sharded (DESIGN.md §13)."""
        if blocks is None:
            return None
        mesh = (self._partition_mesh(self.cfg.partition_shards)
                if self.cfg.partition_active else None)
        if mesh is None:
            return blocks
        from jax.sharding import NamedSharding
        from repro.sharding import rules as _rules
        spec = _rules.owned_span_spec(blocks.ndim, self.cfg.partition_axes)
        return jax.lax.with_sharding_constraint(
            blocks, NamedSharding(mesh, spec))

    def init_grad_buffer(self, state: OptState) -> GradBuffer:
        """Zero-initialized ZeRO-2 gradient accumulator for ``state``
        (DESIGN.md §13): arena grads in the padded flat block domain
        (owned-span sharded on a partition mesh), everything else as
        param-shaped ride-along zeros."""
        cfg = self.cfg
        if not cfg.pooling_active:
            raise ConfigError(
                "GradBuffer accumulation needs the pooled arena layout")
        layout = self._grad_layout(state)
        blocks = None
        part = None
        if state.arena is not None:
            part = state.arena.partition
            segs = state.arena.segments
            total = segs[-1].offset + segs[-1].n_blocks
            rows = part.padded_total if part is not None else total
            blocks = self._constrain_buffer(
                jnp.zeros((rows, cfg.block_size), jnp.float32))
        ride = tuple(jnp.zeros(e[2], jnp.float32)
                     for e in layout if e[0] == "ride")
        return GradBuffer(blocks=blocks, ride=ride, layout=layout,
                          part=part)

    def accumulate_grads(self, buf: GradBuffer, grads: Pytree) -> GradBuffer:
        """Add one microbatch's param-shaped grads into the ZeRO-2 buffer.

        Arena leaves flatten to the block domain and add bucket-by-bucket
        (``cfg.overlap_buckets``): each bucket's add is a separate op whose
        resharding onto the owned-span constraint — the reduce-scatter —
        can fire as soon as that bucket's grads exist, instead of waiting
        on the whole pytree.  Addition commutes with the (exact)
        reshape/pad, so the accumulated values are bit-identical to
        accumulating in param shape and flattening once (DESIGN.md §13)."""
        cfg = self.cfg
        gl = jax.tree_util.tree_leaves(grads)
        if len(gl) != len(buf.layout):
            raise FormatError(f"gradient tree has {len(gl)} leaves but the "
                              f"GradBuffer layout has {len(buf.layout)}")
        gbs = []
        ride = list(buf.ride)
        for g, e in zip(gl, buf.layout):
            if e[0] == "arena":
                gbs.append(flatten_to_blocks(g, cfg.block_size,
                                             cfg.shard_multiple))
            else:
                ride[e[1]] = ride[e[1]] + g.astype(jnp.float32)
        blocks = buf.blocks
        if blocks is not None and gbs:
            gb = jnp.concatenate(gbs) if len(gbs) > 1 else gbs[0]
            pad = blocks.shape[0] - gb.shape[0]
            if pad:
                gb = jnp.pad(gb, ((0, pad), (0, 0)))
            part = buf.part
            if cfg.overlap_active and part is not None:
                plan = make_buckets(part, cfg.overlap_buckets,
                                    grid=max(cfg.shard_multiple, 1))
                b3 = blocks.reshape(part.n_shards, part.span_pad, -1)
                g3 = gb.reshape(part.n_shards, part.span_pad, -1)
                for i, (k0, k1) in enumerate(plan.ranges):
                    with _tracing.annotate(f"grad_bucket{i}"):
                        b3 = b3.at[:, k0:k1].add(g3[:, k0:k1])
                blocks = b3.reshape(blocks.shape)
            else:
                blocks = blocks + gb
            # the owned-span constraint IS the reduce-scatter entry point
            # (DESIGN.md §13): resharding the accumulated buffer onto the
            # partition axes scatters each bucket's sum to its owner
            with _tracing.annotate("reduce_scatter"):
                blocks = self._constrain_buffer(blocks)
        return GradBuffer(blocks=blocks, ride=tuple(ride),
                          layout=buf.layout, part=buf.part)

    def _grad_views(self, grads):
        """Iterate gradient leaves in flatten order as param-shaped views,
        whether ``grads`` is the pytree or a GradBuffer.  Buffer views are
        reshaped back to the original param shape so downstream reductions
        (grad-clip norm, percentile clipping) run the oracle's exact
        per-leaf shapes (DESIGN.md §13)."""
        if not isinstance(grads, GradBuffer):
            for leaf in jax.tree_util.tree_leaves(grads):
                yield leaf
            return
        for e in grads.layout:
            if e[0] == "arena":
                _, off, nb, shape, n = e
                yield grads.blocks[off:off + nb].reshape(-1)[:n].reshape(
                    shape)
            else:
                yield grads.ride[e[1]]

    def grad_buffer_norm(self, buf: GradBuffer) -> jax.Array:
        """Global gradient norm from the ZeRO-2 buffer, bit-identical to
        ``train.loop.global_norm`` on the equivalent param-shaped pytree:
        each leaf's square-sum reduces a view reshaped to the original
        param shape, in flatten order.  On a partition mesh the buffer is
        transiently pinned replicated first so the f32 reduction order
        matches the sequential oracle (the replicate_for_scales contract,
        DESIGN.md §12)."""
        blocks = buf.blocks
        if blocks is not None:
            mesh = (self._partition_mesh(self.cfg.partition_shards)
                    if self.cfg.partition_active else None)
            if mesh is not None:
                from repro.sharding import rules as _rules
                (blocks,) = _rules.replicate_for_scales(mesh, (blocks,))
        buf = GradBuffer(blocks=blocks, ride=buf.ride, layout=buf.layout,
                         part=buf.part)
        sums = [jnp.sum(jnp.square(v.astype(jnp.float32)))
                for v in self._grad_views(buf)]
        return jnp.sqrt(jnp.sum(jnp.stack(sums)))

    def grad_buffer_bytes(self, state: OptState) -> dict:
        """Static peak-gradient accounting (DESIGN.md §13): bytes of the
        replicated param-shaped grad pytree (what the sequential
        accumulator holds) vs the per-device ZeRO-2 share — one owned
        span of the block buffer plus the (replicated) ride-along grads."""
        layout = self._grad_layout(state)
        replicated = ride = 0
        for e in layout:
            if e[0] == "arena":
                replicated += e[4] * 4
            else:
                n = int(np.prod(e[2])) if e[2] else 1
                replicated += n * 4
                ride += n * 4
        rows = 0
        arena = state.arena
        part = arena.partition if arena is not None else None
        if arena is not None:
            segs = arena.segments
            total = segs[-1].offset + segs[-1].n_blocks
            rows = (part.span_pad
                    if part is not None and self.cfg.partition_active
                    else (part.padded_total if part is not None else total))
        sharded = rows * self.cfg.block_size * 4 + ride
        return {"replicated_grad_bytes": int(replicated),
                "sharded_grad_bytes": int(sharded),
                "grad_ride_bytes": int(ride),
                "grad_partition_shards": (part.n_shards
                                          if part is not None and
                                          self.cfg.partition_active else 1)}

    # ---------------------------------------------------------------- update
    def _apply_quant8(self, leaf: Quant8Leaf, g: jax.Array, lr, step_f,
                      seed, gnorm_scale):
        cfg = self.cfg
        gb = flatten_to_blocks(g, cfg.block_size, cfg.shard_multiple)
        # Tell SPMD the reshard target up front: the flat block domain is
        # sharded over ALL mesh axes (EXPERIMENTS.md §Perf A1/A2).
        gb = _constrain(gb, "all", None)

        mdt = jnp.dtype(cfg.master_dtype)
        mb = flatten_to_blocks(leaf.master, cfg.block_size, cfg.shard_multiple)
        mb = _constrain(mb, "all", None)

        # One registry entry point for every algorithm and ablation mode;
        # tensor-wise quantization is dispatched to the jnp entry inside.
        res = kops.fused_update(
            self._ew_algo, mb, gb, leaf.codes_m, leaf.absmax_m,
            leaf.codes_r, leaf.absmax_r, self._qmap1, self._qmap2,
            lr=lr, beta1=cfg.beta1, beta2=cfg.beta2, eps=cfg.eps,
            weight_decay=cfg.weight_decay, step=step_f,
            trust_coeff=cfg.trust_coeff, gnorm_scale=gnorm_scale,
            blockwise=cfg.blockwise_norm,
            stochastic=cfg.stochastic_rounding, seed=seed, impl=self._impl,
            sentinel=cfg.sentinel)
        new = dataclasses.replace(
            leaf, master=blocks_to_param(res.p, leaf.shape, leaf.n, mdt),
            codes_m=res.codes_m, absmax_m=res.absmax_m)
        if res.codes_r is not None:
            new = dataclasses.replace(new, codes_r=res.codes_r,
                                      absmax_r=res.absmax_r)
        # Sentinel (DESIGN.md §16): per-leaf methods return (leaf, h8)
        # where h8 is the (N_HEALTH,) summed HealthFlags vector.
        if cfg.sentinel:
            return new, jnp.sum(res.health, axis=0)
        return new

    def _apply_full32(self, leaf: Full32Leaf, g: jax.Array, lr, step_f,
                      gnorm_scale):
        graw = g.astype(jnp.float32)
        g = graw * gnorm_scale
        r = leaf.r if leaf.r is not None else None
        m2, r2, p2 = self._math32(g, leaf.master, leaf.m, r, lr, step_f)
        new = Full32Leaf(master=p2, m=m2, r=r2)
        if self.cfg.sentinel:
            # Full32 leaves have no codes/absmax: only the nonfinite
            # grad/update slots are meaningful (counted on the raw grad,
            # pre gnorm_scale — inf*0 would mask a nonfinite grad).
            nf = lambda x: jnp.sum((~jnp.isfinite(x)).astype(jnp.float32))
            h8 = jnp.zeros((kfu.N_HEALTH,), jnp.float32)
            h8 = h8.at[0].set(nf(graw)).at[1].set(nf(p2))
            return new, h8
        return new

    def _apply_pool32(self, pool: Pool32Arena, gflat: jax.Array, lr,
                      step_f) -> Pool32Arena:
        """One jnp update for every pooled small leaf at once.  LAMB/LARS
        trust ratios stay per-tensor: each segment's norms are reduced on a
        view reshaped to the original param shape, so the reduction is
        bit-identical to the per-leaf Full32 path.  Under the partitioned
        dispatch (DESIGN.md §12) the per-element math runs span-by-span —
        scales are finalized globally first, so results are unchanged."""
        cfg = self.cfg
        spec = kfu.ALGO_SPECS[self._ew_algo]
        s = kfu.update_scalars(lr=lr, beta1=cfg.beta1, beta2=cfg.beta2,
                               eps=cfg.eps, weight_decay=cfg.weight_decay,
                               step=step_f)
        if spec.needs_norms:
            def seg_scale(i, off, n):
                shape = pool.segments[i].shape
                view = lambda a: a[off:off + n].reshape(shape)
                return kfu.tensor_scale_for(
                    spec, view(gflat), view(pool.master), view(pool.m),
                    None if pool.r is None else view(pool.r), s,
                    cfg.trust_coeff)

            s["tensor_scale"] = kfu.segment_scale_vector(
                [(seg.offset, seg.n) for seg in pool.segments],
                pool.master.shape[0], seg_scale)
        # The fp32 pool is deliberately NOT span-computed under the
        # partitioned dispatch: its leaves are all sub-min_quant_size, so
        # the whole update is a few KB of elementwise work on replicated
        # storage — splitting it buys nothing and embedding it in a
        # different program shape costs ULP-level bit-exactness (XLA FMA
        # contraction is fusion-context dependent).  Its ArenaPartition
        # governs ownership accounting and interchange only (DESIGN.md
        # §12).
        m2, r2, p2 = kfu.update_math(spec, gflat, pool.master, pool.m,
                                     pool.r, s)
        return dataclasses.replace(pool, master=p2, m=m2, r=r2)

    # ------------------------------------------ partitioned (ZeRO-1) dispatch
    def _partition_mesh(self, n_shards: int):
        """The mesh for the shard_map span execution, or None for the
        statically-unrolled fallback (no mesh configured, or the partition
        axes absent / of mismatched total size — the fallback computes
        identical results on any device count).  ``cfg.partition_axes``
        may name several axes ("pod,data" on multi-pod meshes): their size
        product must equal the shard count."""
        mesh = self._mesh
        axes = self.cfg.partition_axes
        if mesh is None or not axes:
            return None
        names = getattr(mesh, "axis_names", ())
        if any(a not in names for a in axes):
            return None
        size = 1
        for a in axes:
            size *= int(mesh.shape[a])
        if size != n_shards:
            return None
        return mesh

    def _fused_update_partitioned(self, arena: QuantArena, mb, gb,
                                  block_seeds, block_offsets, segs, lr,
                                  step_f, gnorm_scale):
        """ZeRO-1 arena update (DESIGN.md §12): every owner updates ONLY
        its owned block span.  Trust ratios (whole-segment norms — a
        segment may straddle span boundaries) are finalized globally once
        and sliced per span, so each span's update is block-local and the
        stitched result is bit-identical to the unpartitioned dispatch.
        With a matching mesh the spans run under shard_map (one local
        fused launch per device; grads reduce-scatter in, master slices
        all-gather out at their use sites); otherwise the spans unroll
        statically — same math, any device count."""
        cfg = self.cfg
        part = arena.partition
        spec = kfu.ALGO_SPECS[self._ew_algo]
        hyper = dict(lr=lr, beta1=cfg.beta1, beta2=cfg.beta2, eps=cfg.eps,
                     weight_decay=cfg.weight_decay, step=step_f,
                     trust_coeff=cfg.trust_coeff, gnorm_scale=gnorm_scale)
        mesh = self._partition_mesh(part.n_shards)
        tscale = None
        if spec.needs_norms:
            sm, sg, scm, sam, scr, sar = (mb, gb, arena.codes_m,
                                          arena.absmax_m, arena.codes_r,
                                          arena.absmax_r)
            if mesh is not None:
                # Pin the scale pass to replicated compute: a whole-
                # segment norm is a global reduction, and letting SPMD
                # distribute it would change the f32 reduction order vs
                # the unpartitioned oracle (ULP drift in trust ratios).
                # Replicated, every device runs the oracle's exact
                # single-device reduction.  (The arena is small — codes;
                # a reduce-then-broadcast of partials is the documented
                # future optimization, DESIGN.md §12.)
                from repro.sharding import rules as _rules
                sm, sg, scm, sam, scr, sar = _rules.replicate_for_scales(
                    mesh, (sm, sg, scm, sam, scr, sar))
            tscale = kops.segment_tensor_scales(
                self._ew_algo, sm, sg, scm, sam, scr, sar,
                self._qmap1, self._qmap2, segments=segs, impl=self._impl,
                **hyper)
        if mesh is not None:
            return self._span_update_shard_map(
                mesh, part, arena, mb, gb, block_seeds, block_offsets,
                tscale, hyper)
        # Bucketed overlap (DESIGN.md §13): subdivide each span into the
        # bucket chunks and fire one launch per (span, bucket) piece —
        # block-local math on static contiguous slices, so the stitched
        # result is bit-identical to the one-launch-per-span dispatch.
        pieces = part.spans
        if cfg.overlap_active:
            plan = make_buckets(part, cfg.overlap_buckets,
                                grid=max(cfg.shard_multiple, 1))
            pieces = [(start + k0, min(n, k1) - k0)
                      for start, n in part.spans
                      for k0, k1 in plan.ranges]
        outs = []
        for i, (start, n) in enumerate(pieces):
            if n <= 0:
                continue
            sl = slice(start, start + n)
            with _tracing.annotate(f"bucket{i}"):
                outs.append(kops.fused_update(
                    self._ew_algo, mb[sl], gb[sl],
                    _slice_blocks(arena.codes_m, start, n),
                    arena.absmax_m[sl],
                    None if arena.codes_r is None
                    else _slice_blocks(arena.codes_r, start, n),
                    None if arena.absmax_r is None else arena.absmax_r[sl],
                    self._qmap1, self._qmap2, blockwise=True,
                    stochastic=cfg.stochastic_rounding,
                    block_seeds=block_seeds[sl],
                    block_offsets=block_offsets[sl],
                    tensor_scale_blocks=None if tscale is None
                    else tscale[sl],
                    impl=self._impl, sentinel=cfg.sentinel, **hyper))
        return _concat_span_results(outs)

    def _span_update_shard_map(self, mesh, part: ArenaPartition,
                               arena: QuantArena, mb, gb, block_seeds,
                               block_offsets, tscale, hyper):
        """shard_map execution of the owned spans: the arena's padded
        block domain splits into one span per device on the partition
        axis; each device runs ONE local fused_update over just its span
        (sharding/rules.py owns the span specs)."""
        from repro.sharding import rules as _rules
        cfg = self.cfg
        axis = cfg.partition_axes
        two = arena.codes_r is not None
        has_ts = tscale is not None

        cm, bits_m, nc_m = lowbit_unwrap(arena.codes_m)
        cr, bits_r, nc_r = lowbit_unwrap(arena.codes_r)
        spans = [mb, gb, cm, arena.absmax_m, block_seeds, block_offsets]
        if two:
            spans += [cr, arena.absmax_r]
        if has_ts:
            spans.append(tscale)
        static = {k: v for k, v in hyper.items()
                  if k not in ("lr", "step", "gnorm_scale")}

        def local(args, consts):
            it = iter(args)
            mb_, gb_, cm_, am_, seeds_, offs_ = (next(it)
                                                 for _ in range(6))
            cr_, ar_ = (next(it), next(it)) if two else (None, None)
            ts_ = next(it) if has_ts else None
            qm1, qm2, lr_, step_, gs_ = consts
            res = kops.fused_update(
                self._ew_algo, mb_, gb_,
                PackedCodes(cm_, bits_m, nc_m) if nc_m is not None else cm_,
                am_,
                None if cr_ is None else (
                    PackedCodes(cr_, bits_r, nc_r) if nc_r is not None
                    else cr_),
                ar_, qm1, qm2, lr=lr_, step=step_, gnorm_scale=gs_,
                blockwise=True, stochastic=cfg.stochastic_rounding,
                block_seeds=seeds_, block_offsets=offs_,
                tensor_scale_blocks=ts_, impl=self._impl,
                sentinel=cfg.sentinel, **static)

            def bare(c):
                return c.packed if isinstance(c, PackedCodes) else c
            out = (res.p, bare(res.codes_m), res.absmax_m)
            if two:
                out += (bare(res.codes_r), res.absmax_r)
            if cfg.sentinel:
                # per-block health rows ride the span machinery like every
                # other block-dim output (stitch/unpad are generic)
                out += (res.health,)
            return out

        consts = (self._qmap1, self._qmap2 if two else self._qmap1,
                  hyper["lr"], hyper["step"], hyper["gnorm_scale"])
        plan = None
        if cfg.overlap_active:
            plan = make_buckets(part, cfg.overlap_buckets,
                                grid=max(cfg.shard_multiple, 1))
        if plan is None or len(plan.ranges) <= 1:
            with _tracing.annotate("span_update"):
                outs = _rules.shard_map_over_spans(
                    mesh, axis, part, local, spans, consts)
        else:
            # Bucketed overlap (DESIGN.md §13): bucket k covers local rows
            # [k0, k1) of EVERY owner's span — the same static shape on
            # each device — so each bucket dispatches as its own shard_map
            # over a synthetic full-span partition.  Stitching the bucket
            # outputs back along the local-row axis reconstructs the
            # padded arena exactly (block-local math: bit-identical to
            # the one-launch-per-span dispatch).
            D, span_pad = part.n_shards, part.span_pad
            pad = part.padded_total - part.total

            def bucket_slice(a, k0, k1):
                a = jnp.asarray(a)
                if pad:
                    a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                a3 = a.reshape((D, span_pad) + a.shape[1:])
                return a3[:, k0:k1].reshape((D * (k1 - k0),) + a.shape[1:])

            per_bucket = []
            for i, (k0, k1) in enumerate(plan.ranges):
                ck = k1 - k0
                bpart = ArenaPartition(
                    n_shards=D, total=D * ck, span_pad=ck,
                    spans=tuple((d * ck, ck) for d in range(D)))
                with _tracing.annotate(f"bucket{i}"):
                    per_bucket.append(_rules.shard_map_over_spans(
                        mesh, axis, bpart, local,
                        [bucket_slice(a, k0, k1) for a in spans], consts))
            outs = []
            for pos in range(len(per_bucket[0])):
                chunks = [b[pos].reshape((D, -1) + b[pos].shape[1:])
                          for b in per_bucket]
                stitched = jnp.concatenate(chunks, axis=1)
                outs.append(stitched.reshape(
                    (part.padded_total,) + stitched.shape[2:])[:part.total])
            outs = tuple(outs)
        p2, cm2, am2 = outs[0], outs[1], outs[2]
        if nc_m is not None:
            cm2 = PackedCodes(cm2, bits_m, nc_m)
        cr2 = ar2 = None
        if two:
            cr2, ar2 = outs[3], outs[4]
            if nc_r is not None:
                cr2 = PackedCodes(cr2, bits_r, nc_r)
        health = outs[5 if two else 3] if cfg.sentinel else None
        return kfu.FusedUpdateResult(p2, cm2, am2, cr2, ar2, health)

    def _route_matrix_leaf(self, owner: int, leaf: Quant8Leaf, g, lr,
                           step_f, seed, gnorm_scale):
        """Whole-leaf owner routing for muon matrix leaves under the
        partitioned dispatch (DESIGN.md §12): on a matching mesh, only the
        owner device runs the Newton–Schulz update; the result broadcasts
        to the replicas (exact — codes are small integers in f32).
        Without a mesh every device computes it, identically."""
        part_shards = max(self.cfg.partition_shards, 1)
        mesh = self._partition_mesh(part_shards)
        fn = self._apply_quant8
        if mesh is None:
            return fn(leaf, g, lr, step_f, seed, gnorm_scale)
        from repro.sharding import rules as _rules
        return _rules.owner_routed(
            mesh, self.cfg.partition_axes, owner, fn,
            (leaf, g, lr, step_f, seed, gnorm_scale))

    def _apply_pooled(self, grads: Pytree, state: OptState, lr, step_f,
                      base_seed, gnorm_scale):
        """One fused_update for the whole QuantArena + one jnp update for
        the Pool32Arena; per-leaf Full32 overrides ride along unchanged.
        Seeds, element indices and trust ratios are threaded per block /
        per segment so the result is bit-identical to the per-leaf
        dispatch (tests/test_pooled.py).

        ``grads`` is either the param-shaped grad pytree or a
        :class:`GradBuffer` (ZeRO-2, DESIGN.md §13) — the buffer already
        holds the arena leaves' grads in the flat block domain, so the
        per-leaf flatten/concat (and its replicated materialization) is
        skipped entirely; ride-along leaves read their param-shaped grads
        from ``buf.ride``."""
        cfg = self.cfg
        mdt = jnp.dtype(cfg.master_dtype)
        buf = grads if isinstance(grads, GradBuffer) else None
        # (N_HEALTH,) HealthFlags contributions from every dispatch this
        # step; summed at the end when cfg.sentinel (DESIGN.md §16).
        health_parts: list = []

        # Walk the leaves once, in flatten order — the same order the
        # per-leaf dispatch numbers its leaves, so seed i matches.
        entries: list = []
        idx = [0]

        def collect(leaf, g):
            entries.append((leaf, g, idx[0]))
            idx[0] += 1
            return leaf

        if buf is None:
            jax.tree_util.tree_map(collect, state.leaves, grads,
                                   is_leaf=_is_state_leaf)
        else:
            layout = iter(buf.layout)

            def collect_buf(leaf):
                ent = next(layout)
                g = buf.ride[ent[1]] if ent[0] == "ride" else None
                return collect(leaf, g)

            jax.tree_util.tree_map(collect_buf, state.leaves,
                                   is_leaf=_is_state_leaf)

        new_arena, res_p = state.arena, None
        if state.arena is not None:
            arena = state.arena
            quant = [(l, g, i) for l, g, i in entries
                     if isinstance(l, PooledQuantLeaf)]
            mbs, seeds, offs = [], [], []
            gbs = [] if buf is None else None
            for leaf, g, i in quant:
                if gbs is not None:
                    gbs.append(flatten_to_blocks(g, cfg.block_size,
                                                 cfg.shard_multiple))
                mbs.append(flatten_to_blocks(leaf.master, cfg.block_size,
                                             cfg.shard_multiple))
                seeds.append(jnp.broadcast_to(
                    base_seed + jnp.int32(i * 7919), (leaf.n_blocks,)))
                offs.append(np.arange(leaf.n_blocks, dtype=np.int32))
            mb = _constrain(jnp.concatenate(mbs), "all", None)
            if buf is None:
                gb = _constrain(jnp.concatenate(gbs), "all", None)
            else:
                # already in arena layout, owned-span sharded — never
                # rebuilt replicated (the ZeRO-2 point)
                gb = buf.blocks[:mb.shape[0]]
            block_seeds = jnp.concatenate(seeds)
            block_offsets = jnp.asarray(np.concatenate(offs))
            segs = tuple((s.offset, s.n_blocks) for s in arena.segments)
            if arena.partition is not None and cfg.partition_active:
                res = self._fused_update_partitioned(
                    arena, mb, gb, block_seeds, block_offsets, segs, lr,
                    step_f, gnorm_scale)
            else:
                res = kops.fused_update(
                    self._ew_algo, mb, gb, arena.codes_m, arena.absmax_m,
                    arena.codes_r, arena.absmax_r, self._qmap1, self._qmap2,
                    lr=lr, beta1=cfg.beta1, beta2=cfg.beta2, eps=cfg.eps,
                    weight_decay=cfg.weight_decay, step=step_f,
                    trust_coeff=cfg.trust_coeff, gnorm_scale=gnorm_scale,
                    blockwise=True, stochastic=cfg.stochastic_rounding,
                    block_seeds=block_seeds, block_offsets=block_offsets,
                    segments=segs, impl=self._impl, sentinel=cfg.sentinel)
            if cfg.sentinel:
                health_parts.append(jnp.sum(res.health, axis=0))
            new_arena = dataclasses.replace(
                arena, codes_m=res.codes_m, absmax_m=res.absmax_m,
                codes_r=res.codes_r if res.codes_r is not None
                else arena.codes_r,
                absmax_r=res.absmax_r if res.absmax_r is not None
                else arena.absmax_r)
            res_p = res.p

        new_pool = state.pool32
        if state.pool32 is not None:
            small_g = [g for l, g, i in entries if isinstance(l, Pool32Leaf)]
            gflat = (jnp.concatenate([g.reshape(-1).astype(jnp.float32)
                                      for g in small_g])
                     if len(small_g) > 1
                     else small_g[0].reshape(-1).astype(jnp.float32))
            new_pool = self._apply_pool32(state.pool32, gflat * gnorm_scale,
                                          lr, step_f)
            if cfg.sentinel:
                # fp32 pool has no codes/absmax; nonfinite grad/update only
                # (raw grads — pre gnorm_scale, as everywhere else).
                nf = lambda x: jnp.sum((~jnp.isfinite(x))
                                       .astype(jnp.float32))
                h8 = jnp.zeros((kfu.N_HEALTH,), jnp.float32)
                health_parts.append(
                    h8.at[0].set(nf(gflat)).at[1].set(nf(new_pool.master)))

        # Second walk re-plays the same flatten order as `collect`, so each
        # ride-along leaf recovers its flatten index i — per-leaf seeds
        # (base + i*7919) therefore match the per-leaf dispatch bit-exactly.
        # Grads come from the entries (works for both pytree and GradBuffer
        # input — the walk is over the leaves alone).
        ent = iter(entries)
        mk = [0]   # matrix-leaf counter: k-th matrix leaf -> owner k % D

        def upd(leaf):
            _, g, i = next(ent)
            if isinstance(leaf, PooledQuantLeaf):
                sl = res_p[leaf.offset:leaf.offset + leaf.n_blocks]
                return dataclasses.replace(
                    leaf, master=blocks_to_param(sl, leaf.shape, leaf.n, mdt))
            if isinstance(leaf, Pool32Leaf):
                return leaf
            if isinstance(leaf, Quant8Leaf):
                # matrix-class (muon) leaves stay per-leaf under the pooled
                # dispatch: each is its own Newton–Schulz problem
                # (DESIGN.md §11).  Partitioned, each is routed whole-leaf
                # to its owner (DESIGN.md §12) — same math, same seed.
                seed = base_seed + jnp.int32(i * 7919)
                if cfg.partition_active:
                    owner = mk[0] % max(cfg.partition_shards, 1)
                    mk[0] += 1
                    out = self._route_matrix_leaf(owner, leaf, g, lr,
                                                  step_f, seed, gnorm_scale)
                else:
                    out = self._apply_quant8(leaf, g, lr, step_f, seed,
                                             gnorm_scale)
            else:
                out = self._apply_full32(leaf, g, lr, step_f, gnorm_scale)
            if cfg.sentinel:
                out, h8 = out
                health_parts.append(h8)
            return out

        new_leaves = jax.tree_util.tree_map(upd, state.leaves,
                                            is_leaf=_is_state_leaf)
        health = _sum_health(health_parts) if cfg.sentinel else None
        return new_leaves, new_arena, new_pool, health

    def apply(self, grads: Pytree, state: OptState, *,
              lr: Optional[jax.Array] = None,
              param_dtype=jnp.float32,
              key: Optional[jax.Array] = None,
              materialize_params: bool = True) -> tuple[Pytree, OptState]:
        """One optimizer step. Returns (new model-shape params, new state).

        ``lr`` overrides cfg.lr (schedules); ``param_dtype`` is the dtype of
        the returned model params (the f32 master stays in the state).
        ``key`` optionally seeds stochastic rounding; when omitted the seed
        is derived from ``state.step``, so restarts from a checkpoint replay
        the same rounding decisions bit-exactly.

        ``grads`` may be a :class:`GradBuffer` (ZeRO-2, DESIGN.md §13;
        pooled layouts only).  ``materialize_params=False`` skips the
        model-shape params reconstruction and returns ``(None, state)`` —
        the deferred-all-gather path: the caller reconstructs via
        :meth:`params_view` at first use (top of the next step), so the
        masters' all-gather overlaps the next forward instead of extending
        this step's tail.

        With ``cfg.sentinel`` (DESIGN.md §16) the return is a 3-tuple
        ``(params, state, health)`` where ``health`` is the summed
        (``kfu.N_HEALTH``,) f32 HealthFlags vector over every dispatch of
        this step (``kfu.HEALTH_SLOTS`` layout).  The OptState pytree is
        unchanged either way — checkpoints and goldens are sentinel-blind.
        """
        cfg = self.cfg
        if isinstance(grads, GradBuffer) and not cfg.pooling_active:
            raise ConfigError(
                "GradBuffer input requires the pooled layout (shard_grads)")
        lr = jnp.asarray(cfg.lr if lr is None else lr, jnp.float32)
        step_f = (state.step + 1).astype(jnp.float32)
        gnorm_scale, new_vec = self.percentile_clip(grads, state)

        if cfg.stochastic_rounding and key is not None:
            base_seed = jax.random.randint(key, (), 0, 2**31 - 1,
                                           dtype=jnp.int32)
        else:
            # int32 wraparound is fine: the seed only feeds a hash.
            base_seed = state.step.astype(jnp.int32) * jnp.int32(1000003)

        if cfg.pooling_active:
            new_leaves, new_arena, new_pool, health = self._apply_pooled(
                grads, state, lr, step_f, base_seed, gnorm_scale)
        else:
            leaf_idx = [0]
            health_parts: list = []

            def upd(leaf, g):
                i = leaf_idx[0]
                leaf_idx[0] += 1
                seed = base_seed + jnp.int32(i * 7919)
                if isinstance(leaf, Quant8Leaf):
                    out = self._apply_quant8(leaf, g, lr, step_f, seed,
                                             gnorm_scale)
                else:
                    out = self._apply_full32(leaf, g, lr, step_f,
                                             gnorm_scale)
                if cfg.sentinel:
                    out, h8 = out
                    health_parts.append(h8)
                return out

            new_leaves = jax.tree_util.tree_map(
                upd, state.leaves, grads, is_leaf=_is_state_leaf)
            new_arena, new_pool = state.arena, state.pool32
            health = _sum_health(health_parts) if cfg.sentinel else None

        new_state = OptState(step=state.step + 1, leaves=new_leaves,
                             gnorm_vec=new_vec, arena=new_arena,
                             pool32=new_pool)
        if not materialize_params:
            return (None, new_state, health) if cfg.sentinel \
                else (None, new_state)
        params = self.params_view(new_state, param_dtype)
        return (params, new_state, health) if cfg.sentinel \
            else (params, new_state)

    def params_view(self, state: OptState, param_dtype=jnp.float32) -> Pytree:
        """Model-shape params reconstructed from the (sharded, flat-block)
        master copies — ZeRO-3 style: no persistent model-shape duplicate;
        XLA inserts the all-gather at use sites.  Pooled small leaves are
        sliced out of the Pool32Arena."""
        pool = state.pool32

        def to_param(leaf):
            if isinstance(leaf, Pool32Leaf):
                sl = pool.master[leaf.offset:leaf.offset + leaf.n]
                return sl.reshape(leaf.shape).astype(param_dtype)
            return leaf.master.astype(param_dtype)

        # the deferred all-gather site (DESIGN.md §13d): reconstructing
        # the model-shape view is where sharded masters re-materialize
        with _tracing.annotate("params_allgather"):
            return jax.tree_util.tree_map(to_param, state.leaves,
                                          is_leaf=_is_state_leaf)

    # ------------------------------------------------------------- utilities
    def state_bytes(self, state: OptState) -> dict:
        """Measured memory of optimizer statistics vs 32-bit equivalent.

        Only static shapes are read, so this also works on abstract/traced
        states (the train loop surfaces ``state_bytes_per_param`` as a
        metric from inside the jitted step)."""

        def codes_bytes(c):
            return c.nbytes() if isinstance(c, PackedCodes) else c.size

        stats = master = n_params = 0
        for leaf in jax.tree_util.tree_leaves(state.leaves,
                                              is_leaf=_is_state_leaf):
            if isinstance(leaf, Quant8Leaf):
                stats += codes_bytes(leaf.codes_m) + leaf.absmax_m.size * 4
                if leaf.codes_r is not None:
                    stats += codes_bytes(leaf.codes_r) + leaf.absmax_r.size * 4
                master += leaf.master.size * leaf.master.dtype.itemsize
                n_params += leaf.n
            elif isinstance(leaf, PooledQuantLeaf):
                # quantized statistics counted once via the arena below
                master += leaf.master.size * leaf.master.dtype.itemsize
                n_params += leaf.n
            elif isinstance(leaf, Pool32Leaf):
                pass  # all state counted via the Pool32Arena below
            else:
                stats += leaf.m.size * 4 + (leaf.r.size * 4 if leaf.r is not None else 0)
                master += leaf.master.size * 4
                n_params += leaf.master.size
        arena = getattr(state, "arena", None)
        if arena is not None:
            stats += codes_bytes(arena.codes_m) + arena.absmax_m.size * 4
            if arena.codes_r is not None:
                stats += codes_bytes(arena.codes_r) + arena.absmax_r.size * 4
        pool = getattr(state, "pool32", None)
        if pool is not None:
            stats += pool.m.size * 4 + (pool.r.size * 4
                                        if pool.r is not None else 0)
            master += pool.master.size * 4
            n_params += pool.master.size
        out = {"state_bytes": int(stats), "master_bytes": int(master),
               "n_params": int(n_params)}
        owned = self._owned_state_bytes(state)
        if owned is not None:
            out.update(owned)
        return out

    def _owned_state_bytes(self, state: OptState) -> Optional[dict]:
        """Partitioned (ZeRO-1) per-device accounting (DESIGN.md §12):
        the largest owner's share of the quantized statistics — its arena
        block span plus the matrix leaves it owns — with the (replicated,
        tiny) fp32 pool and any per-leaf Full32 override counted in full.
        None when partitioning is inactive."""
        arena = getattr(state, "arena", None)
        part = getattr(arena, "partition", None) if arena is not None else None
        if part is None or not self.cfg.partition_active:
            return None

        def codes_bytes_per_block(c):
            if isinstance(c, PackedCodes):
                return c.nbytes() // c.packed.shape[0]
            return int(np.prod(c.shape[1:])) or 1

        per_block = codes_bytes_per_block(arena.codes_m) + 4
        if arena.codes_r is not None:
            per_block += codes_bytes_per_block(arena.codes_r) + 4
        owner_bytes = [n * per_block for _, n in part.spans]
        # muon matrix leaves: whole-leaf ownership, k-th leaf -> k % D
        matrix = [l for l in jax.tree_util.tree_leaves(
            state.leaves, is_leaf=_is_state_leaf)
            if isinstance(l, Quant8Leaf)]
        for k, leaf in enumerate(matrix):
            b = (leaf.codes_m.nbytes()
                 if isinstance(leaf.codes_m, PackedCodes)
                 else leaf.codes_m.size) + leaf.absmax_m.size * 4
            if leaf.codes_r is not None:
                b += (leaf.codes_r.nbytes()
                      if isinstance(leaf.codes_r, PackedCodes)
                      else leaf.codes_r.size) + leaf.absmax_r.size * 4
            owner_bytes[k % part.n_shards] += b
        # replicated remainder: fp32 pool + per-leaf Full32 overrides
        rep = 0
        pool = getattr(state, "pool32", None)
        if pool is not None:
            rep += pool.m.size * 4 + (pool.r.size * 4
                                      if pool.r is not None else 0)
        for leaf in jax.tree_util.tree_leaves(state.leaves,
                                              is_leaf=_is_state_leaf):
            if isinstance(leaf, Full32Leaf):
                rep += leaf.m.size * 4 + (leaf.r.size * 4
                                          if leaf.r is not None else 0)
        return {"partition_shards": part.n_shards,
                "owned_blocks": part.max_owned,
                "owned_state_bytes": int(max(owner_bytes) + rep)}


def _sum_health(parts):
    """Sum per-dispatch (N_HEALTH,) HealthFlags vectors.  Counts are f32
    integers, so the addition is exact in any order (DESIGN.md §16)."""
    if not parts:
        return jnp.zeros((kfu.N_HEALTH,), jnp.float32)
    total = parts[0]
    for p in parts[1:]:
        total = total + p
    return total


def _concat_span_results(outs):
    """Stitch per-span FusedUpdateResults back into the arena layout
    (device-side concat along the block dim, PackedCodes-aware)."""
    if not outs:
        raise FormatError("no non-empty spans to stitch")
    if len(outs) == 1:
        return outs[0]

    def cat(field):
        parts = [getattr(o, field) for o in outs]
        if parts[0] is None:
            return None
        if isinstance(parts[0], PackedCodes):
            return PackedCodes(
                jnp.concatenate([p.packed for p in parts]),
                parts[0].bits, parts[0].n_codes)
        return jnp.concatenate(parts)

    return kfu.FusedUpdateResult(*(cat(f)
                                   for f in kfu.FusedUpdateResult._fields))


# ------------------------------------------------ pooled <-> per-leaf views
# Checkpoints always store the per-leaf canonical layout: `unpool_state`
# slices arenas back into Quant8Leaf / Full32Leaf containers (save side),
# `repool_like` concatenates restored per-leaf arrays into the template's
# arena layout (restore side).  Both work leaf-by-leaf from the static
# segment metadata, so the on-disk format is independent of `cfg.pooled`
# and old per-leaf checkpoints restore into pooled states and vice versa.


def _slice_blocks(x, off: int, nb: int):
    """Block-dim slice [off, off+nb) of an arena child; shape-only on
    ShapeDtypeStruct templates, rewrapping PackedCodes containers."""
    if isinstance(x, PackedCodes):
        return PackedCodes(_slice_blocks(x.packed, off, nb), x.bits,
                           x.n_codes)
    if isinstance(x, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct((nb,) + tuple(x.shape[1:]), x.dtype)
    return x[off:off + nb]


def _slice_flat(x, off: int, n: int, shape: tuple):
    if isinstance(x, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct(tuple(shape), x.dtype)
    return x[off:off + n].reshape(shape)


def unpool_state(state: OptState) -> OptState:
    """Pooled layout -> per-leaf canonical layout (identity for per-leaf
    states).  Accepts concrete arrays or ShapeDtypeStruct templates."""
    arena, pool = state.arena, state.pool32
    if arena is None and pool is None:
        return state

    def conv(leaf):
        if isinstance(leaf, PooledQuantLeaf):
            o, nb = leaf.offset, leaf.n_blocks
            return Quant8Leaf(
                master=leaf.master,
                codes_m=_slice_blocks(arena.codes_m, o, nb),
                absmax_m=_slice_blocks(arena.absmax_m, o, nb),
                codes_r=None if arena.codes_r is None
                else _slice_blocks(arena.codes_r, o, nb),
                absmax_r=None if arena.absmax_r is None
                else _slice_blocks(arena.absmax_r, o, nb),
                shape=leaf.shape, n=leaf.n)
        if isinstance(leaf, Pool32Leaf):
            return Full32Leaf(
                master=_slice_flat(pool.master, leaf.offset, leaf.n,
                                   leaf.shape),
                m=_slice_flat(pool.m, leaf.offset, leaf.n, leaf.shape),
                r=None if pool.r is None
                else _slice_flat(pool.r, leaf.offset, leaf.n, leaf.shape))
        return leaf

    leaves = jax.tree_util.tree_map(conv, state.leaves,
                                    is_leaf=_is_state_leaf)
    return OptState(step=state.step, leaves=leaves,
                    gnorm_vec=state.gnorm_vec, arena=None, pool32=None)


def _concat_rows(parts, like):
    """Host-side concat of per-leaf arena rows, honouring PackedCodes."""
    if isinstance(like, PackedCodes):
        return PackedCodes(
            np.concatenate([np.asarray(p.packed) for p in parts]),
            like.bits, like.n_codes)
    return np.concatenate([np.asarray(p) for p in parts])


def repool_like(per_leaf: OptState, template: OptState) -> OptState:
    """Per-leaf state -> the pooled layout of ``template`` (identity when
    the template is per-leaf).  Used by elastic checkpoint restore; array
    data is concatenated on the host, placement happens afterwards."""
    t_arena, t_pool = template.arena, template.pool32
    if t_arena is None and t_pool is None:
        return per_leaf
    by_block: dict = {}
    by_flat: dict = {}

    def onto(tmpl_leaf, got):
        if isinstance(tmpl_leaf, PooledQuantLeaf):
            by_block[tmpl_leaf.offset] = got
            return dataclasses.replace(tmpl_leaf, master=got.master)
        if isinstance(tmpl_leaf, Pool32Leaf):
            by_flat[tmpl_leaf.offset] = got
            return tmpl_leaf
        return got

    leaves = jax.tree_util.tree_map(onto, template.leaves, per_leaf.leaves,
                                    is_leaf=_is_state_leaf)
    arena = None
    if t_arena is not None:
        parts = [by_block[s.offset] for s in t_arena.segments]
        arena = QuantArena(
            codes_m=_concat_rows([p.codes_m for p in parts],
                                 t_arena.codes_m),
            absmax_m=_concat_rows([p.absmax_m for p in parts],
                                  t_arena.absmax_m),
            codes_r=None if t_arena.codes_r is None
            else _concat_rows([p.codes_r for p in parts], t_arena.codes_r),
            absmax_r=None if t_arena.absmax_r is None
            else _concat_rows([p.absmax_r for p in parts],
                              t_arena.absmax_r),
            segments=t_arena.segments, partition=t_arena.partition)
    pool = None
    if t_pool is not None:
        parts = [by_flat[s.offset] for s in t_pool.segments]

        def flat(xs):
            return np.concatenate([np.asarray(x).reshape(-1) for x in xs])

        pool = Pool32Arena(
            master=flat([p.master for p in parts]),
            m=flat([p.m for p in parts]),
            r=None if t_pool.r is None else flat([p.r for p in parts]),
            segments=t_pool.segments, partition=t_pool.partition)
    return OptState(step=per_leaf.step, leaves=leaves,
                    gnorm_vec=per_leaf.gnorm_vec, arena=arena, pool32=pool)


def map_opt_states(tree, fn):
    """Apply ``fn`` to every OptState inside a checkpointable container
    tree (dicts / lists / (named)tuples), leaving everything else alone."""
    if isinstance(tree, OptState):
        return fn(tree)
    if isinstance(tree, dict):
        return {k: map_opt_states(v, fn) for k, v in tree.items()}
    if isinstance(tree, tuple) and hasattr(tree, "_fields"):
        return type(tree)(*(map_opt_states(v, fn) for v in tree))
    if isinstance(tree, (list, tuple)):
        return type(tree)(map_opt_states(v, fn) for v in tree)
    return tree


def zip_opt_states(tree, template, fn):
    """Parallel walk of ``tree`` and ``template``; applies ``fn(sub,
    template_sub)`` wherever the template holds an OptState."""
    if isinstance(template, OptState):
        return fn(tree, template)
    if isinstance(template, dict):
        return {k: zip_opt_states(tree[k], v, fn)
                for k, v in template.items()}
    if isinstance(template, tuple) and hasattr(template, "_fields"):
        return type(template)(*(zip_opt_states(t, v, fn)
                                for t, v in zip(tree, template)))
    if isinstance(template, (list, tuple)):
        return type(template)(zip_opt_states(t, v, fn)
                              for t, v in zip(tree, template))
    return tree
