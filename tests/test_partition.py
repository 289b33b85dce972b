"""Partitioned (ZeRO-1) pooled optimizer state — DESIGN.md §12.

The contract under test: ``OptimConfig.partition`` changes WHERE each
arena block is updated (each owner updates only its contiguous span; on a
matching mesh, via shard_map with one local fused launch per device) and
nothing else — codes, absmax, masters, stochastic rounding, LAMB/LARS
trust ratios and the percentile-clip history are bit-identical to the
``partition=False`` pooled oracle, on 1-, 2- and 4-device meshes and on
the mesh-free statically-unrolled path (any shard count, including spans
that are padding-only on uneven arenas).  Checkpoints stay per-leaf
canonical, so partitioned ↔ pooled ↔ per-leaf interchange is elastic in
all directions.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.optim import (Quant8Leaf, make_optimizer, make_partition,
                              repool_like, unpool_state)
from repro.kernels import ops
from repro.train import checkpoint as C

from repro.launch.mesh import make_mesh
from helpers import assert_trees_equal, mesh_of


def _params(key=0):
    """Quantized leaves (one straddles span boundaries) + an override
    leaf + small pooled leaves."""
    k = jax.random.PRNGKey(key)
    ks = jax.random.split(k, 5)
    return {
        "dense": {"w": jax.random.normal(ks[0], (64, 128)),
                  "v": jax.random.normal(ks[1], (48, 64))},
        "out": jax.random.normal(ks[2], (96, 32)),
        "embed": {"w": jax.random.normal(ks[3], (128, 64))},   # override
        "bias": jnp.zeros((10,)),                              # pooled fp32
        "small": jax.random.normal(ks[4], (17,)) * 0.1,        # pooled fp32
    }


def _loss(p, target):
    return sum(jnp.sum((a - b) ** 2)
               for a, b in zip(jax.tree_util.tree_leaves(p),
                               jax.tree_util.tree_leaves(target)))


def _train(opt, params, steps=3):
    """Jitted apply steps (the train-step context the dispatch runs in)."""
    target = jax.tree_util.tree_map(lambda p: jnp.ones_like(p) * 0.5, params)
    grad = jax.jit(jax.grad(lambda p: _loss(p, target)))
    step = jax.jit(lambda g, s: opt.apply(g, s))
    st = opt.init(params)
    p = params
    for _ in range(steps):
        p, st = step(grad(p), st)
    return p, st


def _canon(p, st):
    return (p, unpool_state(st).leaves)


# --------------------------------------------------- unrolled bit-exactness
@pytest.mark.parametrize("algo", ["adam", "adamw", "momentum", "lamb",
                                  "lars", "adagrad"])
def test_partitioned_matches_pooled_bit_exact(algo):
    """Mesh-free span dispatch, 3 shards (uneven spans): bitwise equal to
    the pooled oracle incl. stochastic rounding and trust ratios."""
    kw = dict(lr=1e-2, min_8bit_size=1024, stochastic_rounding=True)
    p_a, st_a = _train(make_optimizer(f"{algo}8", partition=True,
                                      partition_shards=3, **kw), _params())
    p_b, st_b = _train(make_optimizer(f"{algo}8", partition=False, **kw),
                       _params())
    assert st_a.arena.partition is not None
    assert st_a.arena.partition.n_shards == 3
    assert_trees_equal(_canon(p_a, st_a), _canon(p_b, st_b), algo)


# ------------------------------------------------------ mesh bit-exactness
@pytest.mark.parametrize("n_dev", [1, 2, 4])
@pytest.mark.parametrize("algo", ["adamw", "lamb"])
def test_partitioned_matches_pooled_on_mesh(algo, n_dev):
    """shard_map span dispatch on a real {1,2,4}-device mesh: one local
    fused update per device, bitwise equal to the oracle (lamb covers the
    globally-finalized trust-ratio path)."""
    mesh = mesh_of(n_dev)
    kw = dict(lr=1e-2, min_8bit_size=1024, stochastic_rounding=True)
    p_a, st_a = _train(make_optimizer(f"{algo}8", mesh=mesh, partition=True,
                                      **kw), _params())
    p_b, st_b = _train(make_optimizer(f"{algo}8", partition=False, **kw),
                       _params())
    assert st_a.arena.partition.n_shards == n_dev
    assert_trees_equal(_canon(p_a, st_a), _canon(p_b, st_b),
                       f"{algo} mesh{n_dev}")


@pytest.mark.parametrize("n_dev", [2, 4])
def test_partitioned_packed_clipping_on_mesh(n_dev):
    """Packed (4, 8) states + percentile clipping on the mesh path: codes,
    absmax, masters AND the clip history stay bit-identical."""
    mesh = mesh_of(n_dev)
    kw = dict(lr=1e-2, min_8bit_size=1024, state_bits=(4, 8),
              stochastic_rounding=True, percentile_clipping=50,
              pclip_history=3)
    p_a, st_a = _train(make_optimizer("adam8", mesh=mesh, **kw),
                       _params(), steps=5)
    p_b, st_b = _train(make_optimizer("adam8", partition=False, **kw),
                       _params(), steps=5)
    assert_trees_equal(_canon(p_a, st_a), _canon(p_b, st_b), "state")
    assert_trees_equal(st_a.gnorm_vec, st_b.gnorm_vec, "gnorm history")


@pytest.mark.parametrize("n_dev", [1, 2, 4])
def test_muon_partitioned_routing_on_mesh(n_dev):
    """Muon matrix leaves route whole-leaf to their owner device (cond +
    broadcast — exact: uint8 codes and f32 state round-trip through the
    psum); the element-wise fallback arena partitions like every other
    algorithm.  Bitwise equal to the unpartitioned oracle."""
    mesh = mesh_of(n_dev)
    kw = dict(lr=1e-2, min_8bit_size=256, override_32bit=lambda p: False,
              stochastic_rounding=True)
    p_a, st_a = _train(make_optimizer("muon8", mesh=mesh, partition=True,
                                      **kw), _params())
    p_b, st_b = _train(make_optimizer("muon8", partition=False, **kw),
                       _params())
    assert_trees_equal(_canon(p_a, st_a), _canon(p_b, st_b),
                       f"muon mesh{n_dev}")


def test_partition_multi_pod_axes():
    """partition_axis="pod,data": the shard_map path activates when the
    PRODUCT of the partition axes matches the shard count — multi-pod
    meshes get the one-local-launch path, not the unrolled fallback —
    and muon owner routing uses the combined (major-to-minor) index."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    mesh = make_mesh((2, 2), ("pod", "data"))
    kw = dict(lr=1e-2, min_8bit_size=1024, stochastic_rounding=True)
    opt = make_optimizer("adamw8", mesh=mesh, partition_axis="pod,data",
                         **kw)
    assert opt.cfg.partition_shards == 4
    assert opt._partition_mesh(4) is mesh      # shard_map path active
    p_a, st_a = _train(opt, _params())
    p_b, st_b = _train(make_optimizer("adamw8", partition=False, **kw),
                       _params())
    assert_trees_equal(_canon(p_a, st_a), _canon(p_b, st_b), "pod,data")
    ops.reset_fused_update_count()
    grads = jax.tree_util.tree_map(lambda p: p * 0.01, _params())
    jax.jit(lambda g, s: opt.apply(g, s)).lower(grads, opt.init(_params()))
    assert ops.fused_update_count() == 1       # ONE local fused launch
    # muon: combined-index owner routing over both axes
    kw_m = dict(lr=1e-2, min_8bit_size=256, override_32bit=lambda p: False,
                stochastic_rounding=True)
    p_m, st_m = _train(make_optimizer("muon8", mesh=mesh,
                                      partition_axis="pod,data", **kw_m),
                       _params(), steps=2)
    p_o, st_o = _train(make_optimizer("muon8", partition=False, **kw_m),
                       _params(), steps=2)
    assert_trees_equal(_canon(p_m, st_m), _canon(p_o, st_o), "muon pod,data")


def test_muon_matrix_owner_assignment():
    """k-th matrix leaf (flatten order) -> owner k % D, recorded with its
    path in the partition metadata."""
    opt = make_optimizer("muon8", lr=1e-2, min_8bit_size=1,
                         override_32bit=lambda p: False,
                         partition=True, partition_shards=2)
    st = opt.init(_params())
    part = (st.arena or st.pool32).partition
    owners = dict(part.matrix_owners)
    # flatten order: bias, dense/v, dense/w, embed/w, out, small — matrix
    # (2-D quantized) leaves among them round-robin over 2 owners
    matrix_paths = [p for p, _ in part.matrix_owners]
    assert [owners[p] for p in matrix_paths] == \
        [k % 2 for k in range(len(matrix_paths))]
    assert len(matrix_paths) >= 3


# ------------------------------------------------- partition metadata/spans
def test_partition_spans_cover_and_align():
    """Spans tile [0, total) contiguously on the grid; uneven totals leave
    trailing spans short or empty (padding-only owners)."""
    part = make_partition(10, 4, grid=4)
    assert part.spans == ((0, 4), (4, 4), (8, 2), (12, 0))
    assert part.padded_total == 16 and part.max_owned == 4
    assert sum(n for _, n in part.spans) == part.total == 10
    part = make_partition(8, 2, grid=1)
    assert part.spans == ((0, 4), (4, 4))
    part = make_partition(3, 4, grid=1)
    assert part.spans == ((0, 1), (1, 1), (2, 1), (3, 0))
    for row, want in ((0, 0), (1, 1), (2, 2)):
        assert part.owner_of(row) == want


def test_uneven_arena_padded_spans_bit_exact():
    """An arena whose block count does not divide the shard count: the
    trailing owner holds a short (padded) span, on the mesh and unrolled
    paths alike."""
    params = {"a": jax.random.normal(jax.random.PRNGKey(0), (80, 64)),
              "b": jax.random.normal(jax.random.PRNGKey(1), (40, 70))}
    kw = dict(lr=1e-2, min_8bit_size=256, override_32bit=lambda p: False,
              stochastic_rounding=True)
    p_o, st_o = _train(make_optimizer("adam8", partition=False, **kw),
                       params)
    p_u, st_u = _train(make_optimizer("adam8", partition=True,
                                      partition_shards=4, **kw), params)
    part = st_u.arena.partition
    assert part.total % part.n_shards != 0      # genuinely uneven
    assert any(n < part.span_pad for _, n in part.spans)
    assert_trees_equal(_canon(p_u, st_u), _canon(p_o, st_o), "unrolled")
    mesh = mesh_of(4)
    p_m, st_m = _train(make_optimizer("adam8", mesh=mesh, partition=True,
                                      **kw), params)
    assert_trees_equal(_canon(p_m, st_m), _canon(p_o, st_o), "mesh")


# ------------------------------------------- launches + owned-bytes metrics
def test_partition_launches_and_owned_bytes():
    """Mesh path: ONE local fused launch per device (trace-time count 1);
    unrolled: one per owned span.  4-way owned statistics <= 0.3x the
    replicated statistics (the acceptance gate)."""
    key = jax.random.PRNGKey(0)
    params = {f"l{i:02d}": jax.random.normal(jax.random.fold_in(key, i),
                                             (8 + (i % 5) * 8, 256))
              for i in range(24)}
    grads = jax.tree_util.tree_map(lambda p: p * 0.01, params)
    kw = dict(lr=1e-3, min_8bit_size=256, override_32bit=lambda p: False)

    def launches(opt):
        st = opt.init(params)
        ops.reset_fused_update_count()
        jax.jit(lambda g, s: opt.apply(g, s)).lower(grads, st)
        return ops.fused_update_count(), opt.state_bytes(st)

    mesh = mesh_of(4)
    n_mesh, sb = launches(make_optimizer("adam8", mesh=mesh, partition=True,
                                         **kw))
    assert n_mesh == 1                       # one LOCAL fused launch
    assert sb["partition_shards"] == 4
    assert sb["owned_state_bytes"] <= 0.3 * sb["state_bytes"]
    n_unrolled, sb_u = launches(make_optimizer(
        "adam8", partition=True, partition_shards=4, **kw))
    assert n_unrolled == 4                   # one per owned span
    assert sb_u["owned_state_bytes"] == sb["owned_state_bytes"]
    n_off, sb_off = launches(make_optimizer("adam8", partition=False, **kw))
    assert n_off == 1 and "owned_state_bytes" not in sb_off


# ----------------------------------------------------- hypothesis property
@pytest.mark.parametrize("shapes,bits,shards", [
    ((( 40, 64), (13, 17), (256,)), None, 2),
    (((96, 32), (7, 300), (64, 64), (2048,)), (4, 8), 3),
    (((130, 70),), (4, 8), 4),
])
def test_partition_stitch_property_cases(shapes, bits, shards):
    _stitch_property(shapes, bits, shards)


def _stitch_property(shapes, bits, shards):
    """build arena -> partition -> local updates stitched back == the
    unpartitioned pooled update, bitwise; and unpool(repool_like(...)) is
    an identity through a partitioned arena."""
    key = jax.random.PRNGKey(hash((tuple(shapes), shards)) % (2 ** 31))
    params = {f"p{i}": jax.random.normal(jax.random.fold_in(key, i), s)
              for i, s in enumerate(shapes)}
    kw = dict(lr=1e-2, min_8bit_size=64, override_32bit=lambda p: False,
              stochastic_rounding=True)
    if bits:
        kw["state_bits"] = bits
    opt_p = make_optimizer("adam8", partition=True, partition_shards=shards,
                           **kw)
    opt_o = make_optimizer("adam8", partition=False, **kw)
    p_a, st_a = _train(opt_p, params, steps=2)
    p_b, st_b = _train(opt_o, params, steps=2)
    assert_trees_equal(_canon(p_a, st_a), _canon(p_b, st_b), "stitch")
    # round trip through the per-leaf canonical form preserves both the
    # arrays and the partition metadata
    back = repool_like(unpool_state(st_a), st_a)
    assert_trees_equal(back, st_a, "repool identity")
    assert back.arena is None or \
        back.arena.partition == st_a.arena.partition


def test_partition_stitch_property_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    dims = st.integers(min_value=3, max_value=160)
    shape = st.one_of(st.tuples(dims, dims), st.tuples(
        st.integers(min_value=64, max_value=4096)))

    @settings(max_examples=8, deadline=None)
    @given(shapes=st.lists(shape, min_size=1, max_size=3),
           bits=st.sampled_from([None, (4, 8), (5, 8)]),
           shards=st.integers(min_value=1, max_value=4))
    def prop(shapes, bits, shards):
        _stitch_property(tuple(tuple(s) for s in shapes), bits, shards)

    prop()


# ------------------------------------------- elastic interchange (ckpt/mesh)
@pytest.mark.parametrize("n_dev", [1, 2, 4])
@pytest.mark.parametrize("state_bits", [None, (4, 8)])
def test_checkpoint_interchange_partitioned_pooled_per_leaf(tmp_path, n_dev,
                                                            state_bits):
    """Save partitioned -> restore pooled AND per-leaf; save per-leaf ->
    restore partitioned; all bit-exact on {1,2,4}-device meshes with an
    uneven arena, and the resumed partitioned step matches the
    uninterrupted pooled run."""
    from repro.sharding import rules
    mesh = mesh_of(n_dev)
    # shard_multiple=n_dev keeps the stored block dim divisible by the
    # mesh (flat_block_spec); partition_shards=3 keeps the OWNED spans
    # uneven regardless, so padded spans are exercised on every mesh.
    kw = dict(lr=1e-2, min_8bit_size=256, override_32bit=lambda p: False,
              shard_multiple=n_dev, stochastic_rounding=True)
    if state_bits:
        kw["state_bits"] = state_bits
    params = {"w": jnp.ones((80, 64)), "v": jnp.ones((40, 32)),
              "b": jnp.zeros((8,))}
    opt_part = make_optimizer("adam8", partition=True, partition_shards=3,
                              **kw)
    opt_pool = make_optimizer("adam8", partition=False, **kw)
    opt_pl = make_optimizer("adam8", pooled=False, **kw)
    _, st = _train(opt_part, params, 3)
    d = str(tmp_path)
    C.save(d, 3, st)

    pshard = jax.tree_util.tree_map(
        lambda p: jax.sharding.NamedSharding(mesh,
                                             jax.sharding.PartitionSpec()),
        params)

    def restore_into(opt):
        template = jax.eval_shape(lambda: opt.init(params))
        shardings = rules.opt_state_shardings(template, pshard, mesh,
                                              rules.ShardingPolicy())
        return C.restore(d, 3, template, shardings)

    st_pool = restore_into(opt_pool)
    st_pl = restore_into(opt_pl)
    assert_trees_equal(unpool_state(st_pool).leaves,
                       unpool_state(st).leaves, "partitioned -> pooled")
    assert_trees_equal(st_pl.leaves, unpool_state(st).leaves,
                       "partitioned -> per-leaf")

    # per-leaf save -> partitioned restore, then a resumed step matches
    # the uninterrupted pooled continuation
    C.save(d, 4, st_pl)
    st_part = restore_into(opt_part)
    assert st_part.arena.partition is not None
    assert_trees_equal(unpool_state(st_part).leaves,
                       unpool_state(st).leaves, "per-leaf -> partitioned")
    target = jax.tree_util.tree_map(lambda p: jnp.ones_like(p) * 0.5, params)
    g = jax.jit(jax.grad(lambda p: _loss(p, target)))(
        opt_pool.params_view(st_pool))
    _, st_a = jax.jit(lambda g, s: opt_part.apply(g, s))(g, st_part)
    _, st_b = jax.jit(lambda g, s: opt_pool.apply(g, s))(g, st_pool)
    assert_trees_equal(unpool_state(st_a).leaves, unpool_state(st_b).leaves,
                       "resumed partitioned step diverged")


@pytest.mark.parametrize("n_dev", [2, 4])
def test_bucketed_packed_overlap_on_mesh(n_dev):
    """DESIGN.md §13: bucketed dispatch (overlap_buckets=3) composed with
    packed (4, 8) states and percentile clipping on the mesh path stays
    bit-identical to the unpartitioned single-dispatch oracle — buckets
    change the launch schedule, never the numerics."""
    mesh = mesh_of(n_dev)
    kw = dict(lr=1e-2, min_8bit_size=1024, state_bits=(4, 8),
              stochastic_rounding=True, percentile_clipping=50,
              pclip_history=3)
    p_a, st_a = _train(make_optimizer("adam8", mesh=mesh,
                                      overlap_buckets=3, **kw),
                       _params(), steps=5)
    p_b, st_b = _train(make_optimizer("adam8", partition=False, **kw),
                       _params(), steps=5)
    assert_trees_equal(_canon(p_a, st_a), _canon(p_b, st_b),
                       f"packed overlap mesh{n_dev}")
    assert_trees_equal(st_a.gnorm_vec, st_b.gnorm_vec, "gnorm history")
