"""Muon matrix-optimizer subsystem (DESIGN.md §11).

Contracts under test:
  * Newton–Schulz kernel parity: Pallas-interpret and jnp NS(5) are
    bit-exact, and the result approximately orthogonalizes.
  * The ("muon", impl) fused-update registry entries are bit-exact across
    impls, incl. stochastic rounding and packed k-bit momentum.
  * Per-leaf routing on a mixed model (2-D, 1-D, sub-min_quantized_size
    leaves): matrix leaves get one-state quantized momentum, element-wise
    leaves fall through to the fused adamw path incl. the pooled arena.
  * pooled == per-leaf, bitwise, and elastic checkpoint interchange on the
    2-device conftest mesh.
  * Quantized Muon trains within 5% of the fp32-Muon loss (smoke task).
  * make_optimizer accepts config objects as the single entry point.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import qmap
from repro.core.lowbit import PackedCodes
from repro.core.optim import (Adafactor, AdafactorConfig, Block8bitOptimizer,
                              Full32Leaf, MuonOptimizer, OptimConfig,
                              Pool32Leaf, PooledQuantLeaf, Quant8Leaf,
                              make_optimizer, unpool_state)
from repro.kernels import newton_schulz as ns
from repro.kernels import ops, ref
from repro.train import checkpoint as C

QS = jnp.asarray(qmap.get_qmap("dynamic", True))


# ----------------------------------------------------- Newton–Schulz kernel
@pytest.mark.parametrize("shape", [(48, 130), (130, 48), (8, 256), (33, 33)])
def test_newton_schulz_parity_interpret_jnp(shape):
    """Tiled Pallas NS(5) (interpret) == the tile-replaying jnp path,
    bit-for-bit — incl. non-tile-multiple shapes and the transpose path."""
    x = jax.random.normal(jax.random.PRNGKey(0), shape)
    oj = ns.newton_schulz(x, impl="jnp")
    oi = ns.newton_schulz(x, impl="interpret")
    np.testing.assert_array_equal(np.asarray(oj), np.asarray(oi))


def test_newton_schulz_orthogonalizes():
    """NS(5) with the Muon quintic drives the singular values into a band
    around 1 and lands near the polar factor UV^T."""
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 192))
    o = np.asarray(ns.newton_schulz(x, impl="jnp"), np.float64)
    s = np.linalg.svd(o, compute_uv=False)
    assert 0.3 < s.min() and s.max() < 1.4, (s.min(), s.max())
    u, _, vt = np.linalg.svd(np.asarray(x, np.float64), full_matrices=False)
    tgt = u @ vt
    cos = (o * tgt).sum() / (np.linalg.norm(o) * np.linalg.norm(tgt))
    assert cos > 0.95, cos


def test_newton_schulz_ref_is_jnp_path():
    x = jax.random.normal(jax.random.PRNGKey(2), (16, 40))
    np.testing.assert_array_equal(
        np.asarray(ref.newton_schulz_ref(x)),
        np.asarray(ns.newton_schulz(x, impl="jnp")))


def test_rms_scale():
    assert ns.rms_scale((128, 64)) == pytest.approx(2 ** 0.5)
    assert ns.rms_scale((64, 128)) == 1.0


# ------------------------------------------------- fused muon registry entry
def _muon_inputs(shape, seed=0, bits=8):
    k = jax.random.PRNGKey(seed)
    ks = jax.random.split(k, 3)
    p = jax.random.normal(ks[0], shape)
    g = jax.random.normal(ks[1], shape) * 0.1
    n = shape[0] * shape[1]
    nb, bsz = -(-n // 256), 256
    qmap_m = jnp.asarray(qmap.get_qmap("dynamic", True, bits=bits))
    m0 = jnp.pad(jax.random.normal(ks[2], (n,)) * 0.01,
                 (0, nb * bsz - n)).reshape(nb, bsz)
    cm, am = ref.quantize_ref(m0, qmap_m)
    if bits < 8:
        cm = PackedCodes.from_codes(cm, bits)
    return p, g, cm, am, qmap_m


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("stochastic", [False, True])
def test_muon_fused_update_parity(bits, stochastic):
    """("muon", interpret) == ("muon", jnp) bit-for-bit: params, codes,
    absmax — incl. stochastic rounding and packed 4-bit momentum."""
    p, g, cm, am, qm = _muon_inputs((48, 66), bits=bits)
    kw = dict(lr=1e-2, beta1=0.95, weight_decay=0.01, gnorm_scale=0.7,
              stochastic=stochastic, seed=123)
    a = ops.fused_update("muon", p, g, cm, am, qmap_m=qm, impl="interpret",
                         **kw)
    b = ops.fused_update("muon", p, g, cm, am, qmap_m=qm, impl="jnp", **kw)
    for name, x1, x2 in zip(a._fields, a, b):
        if x1 is None:
            assert x2 is None, name
            continue
        if isinstance(x1, PackedCodes):
            assert x1.bits == bits == x2.bits
            x1, x2 = x1.packed, x2.packed
        np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2),
                                      err_msg=name)
    if stochastic:
        c = ops.fused_update("muon", p, g, cm, am, qmap_m=qm, impl="jnp",
                             **{**kw, "seed": 124})
        c1 = c.codes_m.packed if bits < 8 else c.codes_m
        b1 = b.codes_m.packed if bits < 8 else b.codes_m
        assert int((np.asarray(c1) != np.asarray(b1)).sum()) > 0


def test_muon_registered_all_impls():
    assert [("muon", i) for i in ("interpret", "jnp", "pallas")] == \
        ops.registered("muon")
    from repro.kernels import fused_update as kfu
    assert kfu.ALGO_SPECS["muon"].matrix
    assert kfu.ALGO_SPECS["muon"].n_states == 1


def test_muon_rejects_tensorwise():
    p, g, cm, am, qm = _muon_inputs((16, 16))
    with pytest.raises(NotImplementedError):
        ops.fused_update("muon", p, g, cm, am, qmap_m=qm, lr=1e-2,
                         blockwise=False, impl="jnp")
    with pytest.raises(ValueError):
        make_optimizer("muon8", blockwise_norm=False)


def test_base_engine_rejects_matrix_algo():
    """Constructing the element-wise engine directly with a matrix-class
    algo must fail loudly — it has no matrix routing, and the flat block
    arena is 2-D, so Newton–Schulz would silently orthogonalize it."""
    with pytest.raises(ValueError, match="matrix-class"):
        Block8bitOptimizer(OptimConfig(algo="muon", bits=8))


# ------------------------------------------------ mixed-class engine routing
def _params(key=0):
    """2-D (muon), 1-D quantized (adamw arena), sub-min (fp32 pool/leaf),
    and an embedding override (adamw fp32)."""
    k = jax.random.PRNGKey(key)
    ks = jax.random.split(k, 5)
    return {
        "dense": {"w": jax.random.normal(ks[0], (64, 128)),
                  "v": jax.random.normal(ks[1], (48, 64))},
        "vec": jax.random.normal(ks[2], (2048,)),
        "embed": {"w": jax.random.normal(ks[3], (128, 64))},
        "bias": jnp.zeros((10,)),
        "small2d": jax.random.normal(ks[4], (4, 4)) * 0.1,
    }


def _loss(p, target):
    return sum(jnp.sum((a - b) ** 2)
               for a, b in zip(jax.tree_util.tree_leaves(p),
                               jax.tree_util.tree_leaves(target)))


def _train(opt, params, steps=3):
    target = jax.tree_util.tree_map(lambda p: jnp.ones_like(p) * 0.5, params)
    grad = jax.jit(jax.grad(lambda p: _loss(p, target)))
    st = opt.init(params)
    p = params
    for _ in range(steps):
        p, st = opt.apply(grad(p), st)
    return p, st


def _assert_trees_equal(a, b, msg=""):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), msg
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=msg)


def test_muon_routing_table():
    """The per-leaf routing split (DESIGN.md §11): 2-D leaves carry a
    single quantized momentum slot; element-wise leaves keep the existing
    adamw containers (pooled arena / fp32 pool / Full32 override)."""
    opt = make_optimizer("muon8", lr=1e-2, min_8bit_size=1024)
    assert isinstance(opt, MuonOptimizer)
    st = opt.init(_params())
    lv = st.leaves
    assert isinstance(lv["dense"]["w"], Quant8Leaf)       # matrix, per-leaf
    assert lv["dense"]["w"].codes_r is None               # one-state
    assert isinstance(lv["vec"], PooledQuantLeaf)         # ew -> arena
    assert st.arena is not None and st.arena.codes_r is not None  # adamw
    assert isinstance(lv["embed"]["w"], Full32Leaf)       # override
    assert lv["embed"]["w"].r is not None                 # ...adamw, 2-state
    assert isinstance(lv["bias"], Pool32Leaf)             # sub-min 1-D
    assert isinstance(lv["small2d"], Full32Leaf)          # sub-min 2-D
    assert lv["small2d"].r is None                        # ...fp32 muon
    # fp32-Muon baseline: every matrix leaf is a one-state Full32Leaf
    st32 = make_optimizer("muon32", lr=1e-2).init(_params())
    assert st32.leaves["dense"]["w"].r is None
    assert st32.leaves["vec"].r is not None


@pytest.mark.parametrize("state_bits", [None, (4, 8)])
def test_muon_pooled_matches_per_leaf_bit_exact(state_bits):
    """Pooled apply == per-leaf apply bitwise on the mixed model, incl.
    stochastic rounding and packed momentum (flatten-order seeds match)."""
    kw = dict(lr=1e-2, min_8bit_size=1024, stochastic_rounding=True)
    if state_bits:
        kw["state_bits"] = state_bits
    p_a, st_a = _train(make_optimizer("muon8", pooled=True, **kw), _params())
    p_b, st_b = _train(make_optimizer("muon8", pooled=False, **kw), _params())
    assert st_a.arena is not None and st_a.pool32 is not None
    _assert_trees_equal(p_a, p_b, "params")
    _assert_trees_equal(unpool_state(st_a).leaves, st_b.leaves, "state")


def test_muon_dispatch_count():
    """Pooled muon step = one fused arena launch (all ew leaves) + one NS
    launch per matrix leaf — the ew fallback still pools (DESIGN.md §11)."""
    params = _params()
    target = jax.tree_util.tree_map(lambda p: jnp.ones_like(p) * 0.5, params)
    grad = jax.grad(lambda p: _loss(p, target))(params)
    opt = make_optimizer("muon8", lr=1e-2, min_8bit_size=1024)
    st = opt.init(params)
    ops.reset_fused_update_count()
    jax.jit(lambda g, s: opt.apply(g, s)).lower(grad, st)   # trace only
    n_matrix = 2    # dense/w, dense/v
    assert ops.fused_update_count() == n_matrix + 1


def test_muon_state_bytes_one_state_momentum():
    """Measured memory: a quantized matrix leaf stores ~bits_m/8 bytes per
    param of statistics (single momentum slot), vs 2 slots for adamw."""
    p = {"w": jnp.zeros((512, 64))}     # 32768 elems, 16 blocks
    opt = make_optimizer("muon8", lr=1e-2, min_8bit_size=1,
                         override_32bit=lambda s: False)
    sb = opt.state_bytes(opt.init(p))
    n = 512 * 64
    assert sb["state_bytes"] == pytest.approx(n * (1 + 4 / 2048), rel=1e-6)
    opt4 = make_optimizer("muon8", lr=1e-2, min_8bit_size=1,
                          override_32bit=lambda s: False, state_bits=(4, 8))
    sb4 = opt4.state_bytes(opt4.init(p))
    assert sb4["state_bytes"] == pytest.approx(n * (0.5 + 4 / 2048),
                                               rel=1e-6)


# --------------------------------------------- checkpoint + sharding (mesh)
from helpers import mesh_of as _mesh_of  # noqa: E402  (shared sub-meshes)


@pytest.mark.parametrize("n_dev", [1, 2, 4])
@pytest.mark.parametrize("state_bits", [None, (4, 8)])
def test_muon_checkpoint_interchange_on_mesh(tmp_path, state_bits, n_dev):
    """Save per-leaf muon -> restore pooled on {1,2,4}-device meshes (and
    the resumed step stays bit-exact vs the uninterrupted run), incl.
    packed momentum.  Matrix momentum leaves shard their block dim like
    every other quantized state."""
    from repro.sharding import rules
    mesh = _mesh_of(n_dev)
    kw = dict(lr=1e-2, min_8bit_size=256, override_32bit=lambda p: False,
              shard_multiple=n_dev, stochastic_rounding=True)
    if state_bits:
        kw["state_bits"] = state_bits
    params = {"w": jnp.ones((64, 64)), "v": jax.random.normal(
        jax.random.PRNGKey(0), (48, 32)), "b": jnp.zeros((8,)),
        "vec": jnp.ones((512,))}
    opt_pl = make_optimizer("muon8", pooled=False, **kw)
    opt_po = make_optimizer("muon8", pooled=True, **kw)
    _, st = _train(opt_pl, params, 3)
    d = str(tmp_path)
    C.save(d, 3, st)

    template = jax.eval_shape(lambda: opt_po.init(params))
    pshard = jax.tree_util.tree_map(
        lambda p: jax.sharding.NamedSharding(mesh,
                                             jax.sharding.PartitionSpec()),
        params)
    shardings = rules.opt_state_shardings(template, pshard, mesh,
                                          rules.ShardingPolicy())
    # matrix momentum leaves: block dim over the mesh
    wshard = shardings.leaves["w"]
    got = wshard.codes_m.packed if state_bits else wshard.codes_m
    # jax >= 0.9 normalizes a one-axis tuple entry to the bare axis name
    assert got.spec[0] in ("data", ("data",))
    st_po = C.restore(d, 3, template, shardings)
    _assert_trees_equal(unpool_state(st_po).leaves, st.leaves,
                        "restored pooled != saved per-leaf")
    target = jax.tree_util.tree_map(lambda p: jnp.ones_like(p) * 0.5, params)
    g = jax.jit(jax.grad(lambda p: _loss(p, target)))(opt_pl.params_view(st))
    _, st_a = opt_pl.apply(g, st)
    _, st_b = opt_po.apply(g, st_po)
    _assert_trees_equal(st_a.leaves, unpool_state(st_b).leaves,
                        "resumed step diverged")


# --------------------------------------------------------- smoke-task gate
def test_muon8_within_5pct_of_muon32_on_smoke_train_task():
    """Acceptance: quantized Muon converges within 5% of fp32-Muon loss on
    the smoke LM task (same seeds, same data)."""
    from benchmarks.common import small_lm, train_lm
    cfg, pipe = small_lm(vocab=128, d_model=64, seq=32, batch=8)
    l32, _, d32 = train_lm(cfg, pipe, "muon32", steps=25, lr=2e-2)
    l8, _, d8 = train_lm(cfg, pipe, "muon8", steps=25, lr=2e-2)
    assert not d32 and not d8
    assert abs(l8 - l32) / l32 < 0.05, (l8, l32)


# -------------------------------------------------- make_optimizer(config)
def test_make_optimizer_accepts_config_objects():
    """The single construction entry point dispatches on the config type /
    algo — Block8bit, Muon and Adafactor all construct through it."""
    assert isinstance(make_optimizer(OptimConfig(algo="adam", bits=8)),
                      Block8bitOptimizer)
    opt = make_optimizer(OptimConfig(algo="muon", bits=8), lr=3e-3)
    assert isinstance(opt, MuonOptimizer) and opt.cfg.lr == 3e-3
    assert isinstance(make_optimizer(AdafactorConfig(lr=1e-3)), Adafactor)
    # name path recurses through the config path (same defaults)
    assert isinstance(make_optimizer("muon32"), MuonOptimizer)
    with pytest.raises(ValueError, match="unknown optimizer"):
        make_optimizer("muon16")


def test_muon_train_step_metrics():
    """Muon rides the train loop unchanged: state_bytes_per_param and the
    dispatch-count metric come out of the jitted step."""
    from benchmarks.common import small_lm
    from repro.train import loop as L
    cfg, pipe = small_lm(vocab=128, d_model=64, seq=32, batch=8)
    opt = make_optimizer("muon8", lr=1e-3, min_8bit_size=1024)
    state, _ = L.init_train_state(cfg, opt, jax.random.PRNGKey(0))
    step = jax.jit(L.make_train_step(cfg, opt))
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    _, m = step(state, batch)
    sb = opt.state_bytes(state.opt_state)
    assert float(m["state_bytes_per_param"]) == pytest.approx(
        sb["state_bytes"] / sb["n_params"], rel=1e-6)
    assert float(m["opt_fused_dispatches"]) >= 1
    assert np.isfinite(float(m["loss"]))
