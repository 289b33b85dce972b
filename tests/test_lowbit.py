"""k-bit code-format subsystem (DESIGN.md §9): qmap level counts, pack/
unpack round-trips (property-style over odd block counts, all bitwidths),
kernel parity for packed states, optimizer wiring, checkpoint elastic
restore of packed leaves, and sharding rules for packed arrays."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import qmap
from repro.core.lowbit import (PACK_LAYOUT, SUPPORTED_BITS, CodeFormat,
                               PackedCodes, pack_codes, packed_width,
                               unpack_codes)
from repro.errors import ConfigError, FormatError
from repro.core.optim import (Full32Leaf, OptimConfig, Quant8Leaf,
                              make_optimizer, unpool_state)
from repro.kernels import ops, ref
from repro.launch.mesh import make_mesh


# ------------------------------------------------------------------- qmaps
@pytest.mark.parametrize("bits", SUPPORTED_BITS)
@pytest.mark.parametrize("signed", [True, False])
def test_kbit_qmap_levels(bits, signed):
    for name in ["dynamic", "inverse_dynamic", "linear", "quantile_normal"]:
        m = qmap.get_qmap(name, signed, bits=bits)
        assert m.shape == (2 ** bits,)
        assert np.all(np.diff(m) >= 0)
        assert m[-1] == pytest.approx(1.0)


@pytest.mark.parametrize("bits", SUPPORTED_BITS)
def test_kbit_dynamic_map_has_zero_and_sign_structure(bits):
    s = qmap.get_qmap("dynamic", True, bits=bits)
    assert 0.0 in s and 1.0 in s
    # signed map is (almost) antisymmetric: every positive level has its
    # mirror except the appended 1.0
    pos = s[s > 0]
    neg = -s[s < 0]
    np.testing.assert_allclose(np.sort(pos)[:-1], np.sort(neg), rtol=1e-6)


def test_default_qmap_unchanged():
    """bits=8 must reproduce the paper's 256-entry maps bit-for-bit."""
    np.testing.assert_array_equal(qmap.get_qmap("dynamic", True),
                                  qmap.get_qmap("dynamic", True, bits=8))
    assert qmap.get_qmap("dynamic", True).shape == (256,)


# ----------------------------------------------------------------- packing
@pytest.mark.parametrize("bits", SUPPORTED_BITS)
@pytest.mark.parametrize("n_blocks", [1, 3, 5, 7, 13])
def test_pack_unpack_roundtrip_odd_block_counts(bits, n_blocks):
    """Property-style sweep: random codes over odd block counts round-trip
    exactly for every supported bitwidth."""
    rng = np.random.RandomState(bits * 100 + n_blocks)
    for bsz in (8, 24, 256):
        codes = rng.randint(0, 2 ** bits, size=(n_blocks, bsz))
        packed = pack_codes(jnp.asarray(codes), bits)
        assert packed.shape == (n_blocks, packed_width(bsz, bits))
        assert packed.dtype == jnp.uint8
        out = unpack_codes(packed, bits)
        np.testing.assert_array_equal(np.asarray(out), codes)


@pytest.mark.parametrize("bits,codes,packed", [
    # 4-bit: first half of the row in the high nibbles, second in the low
    (4, [1, 2, 3, 4], [0x13, 0x24]),
    # 6-bit: four lane chunks, one per code, three bytes of bitstream
    (6, [1, 2, 3, 4], [0b00000100, 0b00100000, 0b11000100]),
])
def test_pack_layout_is_lane_chunked(bits, codes, packed):
    """The lane-chunked layout: position j of every chunk forms one group,
    so packing needs only static lane slices (the Mosaic-lowerable form)."""
    got = pack_codes(jnp.asarray([codes]), bits)
    np.testing.assert_array_equal(np.asarray(got)[0], packed)


def test_pack_unpack_roundtrip_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=50, deadline=None)
    @given(bits=st.sampled_from(SUPPORTED_BITS),
           n_blocks=st.integers(1, 9),
           bsz_mult=st.integers(1, 8),
           seed=st.integers(0, 2 ** 16))
    def roundtrip(bits, n_blocks, bsz_mult, seed):
        bsz = 8 * bsz_mult
        rng = np.random.RandomState(seed)
        codes = rng.randint(0, 2 ** bits, size=(n_blocks, bsz))
        out = unpack_codes(pack_codes(jnp.asarray(codes), bits), bits)
        np.testing.assert_array_equal(np.asarray(out), codes)

    roundtrip()


def test_packed_codes_container():
    codes = jnp.asarray(np.random.RandomState(0).randint(0, 16, (5, 64)))
    pc = PackedCodes.from_codes(codes, 4)
    assert pc.shape == (5, 64)
    assert pc.packed.shape == (5, 32)
    assert pc.nbytes() == 5 * 32
    np.testing.assert_array_equal(np.asarray(pc.unpack()), np.asarray(codes))
    # pytree: exactly one array leaf, static aux survives a map
    leaves = jax.tree_util.tree_leaves(pc)
    assert len(leaves) == 1
    pc2 = jax.tree_util.tree_map(lambda x: x, pc)
    assert (pc2.bits, pc2.n_codes) == (4, 64)


def test_code_format_accounting():
    f4 = CodeFormat(bits=4, signed=True)
    f8 = CodeFormat(bits=8, signed=True)
    assert f4.n_levels == 16 and f4.max_code == 15
    assert f4.bytes_per_param(2048) < 0.55 * f8.bytes_per_param(2048)
    init = f4.init_codes(6, 2048)
    assert isinstance(init, PackedCodes)
    assert np.all(np.asarray(init.unpack()) == f4.zero_code())
    assert isinstance(f8.init_codes(6, 2048), jnp.ndarray)


# ----------------------------------------------------------- kernel parity
HYPER = dict(lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
             weight_decay=0.01, step=7.0, trust_coeff=1e-3)


def _kbit_inputs(algo, bits, nb=3, bsz=256):
    qs = jnp.asarray(qmap.get_qmap("dynamic", True, bits=bits))
    qu = jnp.asarray(qmap.get_qmap("dynamic", False, bits=bits))
    k = jax.random.PRNGKey(0)
    p = jax.random.normal(k, (nb, bsz))
    g = jax.random.normal(jax.random.PRNGKey(1), (nb, bsz)) * 0.01
    two = algo in ("adam", "adamw", "lamb")
    q1 = qu if algo == "adagrad" else qs
    x1 = jnp.abs(p) * 1e-3 if algo == "adagrad" else p * 0.01
    c1, a1 = ref.quantize_ref(x1, q1)
    cm = PackedCodes.from_codes(c1, bits)
    cr = ar = None
    if two:
        c2, a2 = ref.quantize_ref(jnp.abs(p) * 1e-4, qu)
        cr, ar = PackedCodes.from_codes(c2, bits), a2
    return p, g, cm, a1, cr, ar, q1, qu


@pytest.mark.parametrize("bits", [4, 5, 6])
@pytest.mark.parametrize("algo", ["adam", "lamb", "adagrad"])
def test_kbit_fused_update_parity(bits, algo):
    """Packed k-bit fused update: Pallas-interpret (in-kernel unpack/pack)
    vs the jnp oracle must agree bit-for-bit on the packed codes."""
    args = _kbit_inputs(algo, bits)
    out_k = ops.fused_update(algo, *args, impl="interpret", **HYPER)
    out_r = ops.fused_update(algo, *args, impl="jnp", **HYPER)
    assert isinstance(out_k.codes_m, PackedCodes)
    assert out_k.codes_m.packed.shape == (3, 256 * bits // 8)
    np.testing.assert_array_equal(np.asarray(out_k.codes_m.packed),
                                  np.asarray(out_r.codes_m.packed))
    np.testing.assert_allclose(np.asarray(out_k.p), np.asarray(out_r.p),
                               atol=5e-6, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out_k.absmax_m),
                               np.asarray(out_r.absmax_m),
                               atol=5e-6, rtol=1e-5)


def test_kbit_fused_update_stochastic_parity():
    args = _kbit_inputs("adam", 4)
    out_k = ops.fused_update("adam", *args, impl="interpret",
                             stochastic=True, seed=123, **HYPER)
    out_r = ops.fused_update("adam", *args, impl="jnp",
                             stochastic=True, seed=123, **HYPER)
    np.testing.assert_array_equal(np.asarray(out_k.codes_m.packed),
                                  np.asarray(out_r.codes_m.packed))


def test_kbit_qmap_level_mismatch_rejected():
    args = list(_kbit_inputs("adam", 4))
    args[6] = jnp.asarray(qmap.get_qmap("dynamic", True, bits=5))  # qmap_m
    with pytest.raises(ValueError, match="levels"):
        ops.fused_update("adam", *args, impl="jnp", **HYPER)


# -------------------------------------------------------- optimizer wiring
def _params():
    k = jax.random.PRNGKey(0)
    return {"dense": {"w": jax.random.normal(k, (64, 128))},
            "bias": jnp.zeros((10,))}


def _loss(p, target):
    return sum(jnp.sum((a - b) ** 2)
               for a, b in zip(jax.tree_util.tree_leaves(p),
                               jax.tree_util.tree_leaves(target)))


def test_state_bits_containers_and_bytes():
    opt8 = make_optimizer("adam8", lr=1e-3, min_8bit_size=1024,
                          override_32bit=lambda p: False)
    opt4 = make_optimizer("adam8", lr=1e-3, min_8bit_size=1024,
                          override_32bit=lambda p: False, state_bits=(4, 8))
    st8, st4 = opt8.init(_params()), opt4.init(_params())
    assert isinstance(st4.arena.codes_m, PackedCodes)
    assert st4.arena.codes_m.bits == 4
    leaf = unpool_state(st4).leaves["dense"]["w"]
    assert isinstance(leaf, Quant8Leaf)
    assert isinstance(leaf.codes_m, PackedCodes) and leaf.codes_m.bits == 4
    assert not isinstance(leaf.codes_r, PackedCodes)  # 8-bit slot unchanged
    b8 = opt8.state_bytes(st8)
    b4 = opt4.state_bytes(st4)
    assert b8["n_params"] == b4["n_params"]
    # packed m is half the bytes; r and absmax shared
    full4 = make_optimizer("adam8", lr=1e-3, min_8bit_size=1024,
                           override_32bit=lambda p: False, state_bits=4)
    bf4 = full4.state_bytes(full4.init(_params()))
    assert bf4["state_bytes"] <= 0.55 * b8["state_bytes"]


def test_state_bits_config_validation():
    # typed exception (repro.errors): asserts vanish under python -O
    with pytest.raises(ConfigError):
        OptimConfig(algo="adam", state_bits=3)
    assert OptimConfig(algo="adam", state_bits=4).state_bits_pair == (4, 4)
    assert OptimConfig(algo="adam",
                       state_bits=(4, 8)).state_bits_pair == (4, 8)
    cfg = OptimConfig(algo="adam", state_bits=(4, 8), block_size=2048)
    assert cfg.state_bytes_per_param() == pytest.approx(
        0.5 + 1.0 + 2 * 4 / 2048)


def test_min_quantized_size_canonical_name():
    """bitsandbytes-style small-tensor threshold under its canonical name;
    the legacy min_8bit_size keeps working as an alias."""
    opt = make_optimizer("adam8", lr=1e-3, min_quantized_size=32,
                         override_32bit=lambda p: False)
    st = unpool_state(opt.init({"big": jnp.zeros((64,)),
                                "small": jnp.zeros((8,))}))
    assert isinstance(st.leaves["big"], Quant8Leaf)
    assert isinstance(st.leaves["small"], Full32Leaf)
    # canonical name wins over the alias
    assert OptimConfig(min_quantized_size=7, min_8bit_size=9).min_quant_size == 7
    assert OptimConfig(min_8bit_size=9).min_quant_size == 9


@pytest.mark.parametrize("bits", [(4, 8), 6])
def test_kbit_adam_converges(bits):
    params = _params()
    target = jax.tree_util.tree_map(lambda p: jnp.ones_like(p) * 0.5, params)
    opt = make_optimizer("adam8", lr=3e-2, min_8bit_size=1024,
                         state_bits=bits)
    st = opt.init(params)
    grad = jax.jit(jax.grad(lambda p: _loss(p, target)))
    p = params
    l0 = float(_loss(p, target))
    for _ in range(100):
        p, st = opt.apply(grad(p), st)
    # 16-level first-moment codes cap the final precision on a synthetic
    # quadratic; a 4x reduction shows the packed update is *optimizing*
    # (the 5%-of-8-bit acceptance runs on the LM smoke task below).
    assert float(_loss(p, target)) < 0.25 * l0


def test_kbit_matches_8bit_on_smoke_train_task():
    """Acceptance: 4-bit(m)/8-bit(r) Adam converges within 5% of the 8-bit
    loss curve on the smoke LM task."""
    from benchmarks.common import small_lm, train_lm
    cfg, pipe = small_lm(vocab=128, d_model=64, seq=32, batch=8)
    l8, _, d8 = train_lm(cfg, pipe, "adam8", steps=25)
    l4, _, d4 = train_lm(cfg, pipe, "adam8", steps=25, state_bits=(4, 8))
    assert not d8 and not d4
    assert abs(l4 - l8) / l8 < 0.05


def test_state_bytes_per_param_metric():
    """train/loop surfaces measured state bytes/param from inside jit."""
    from benchmarks.common import small_lm
    from repro.train import loop as L
    cfg, pipe = small_lm(vocab=128, d_model=64, seq=32, batch=8)
    opt = make_optimizer("adam8", lr=1e-3, min_8bit_size=1024,
                         state_bits=(4, 8))
    state, _ = L.init_train_state(cfg, opt, jax.random.PRNGKey(0))
    step = jax.jit(L.make_train_step(cfg, opt))
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    _, m = step(state, batch)
    sb = opt.state_bytes(state.opt_state)
    assert float(m["state_bytes_per_param"]) == pytest.approx(
        sb["state_bytes"] / sb["n_params"], rel=1e-6)


# ------------------------------------------------- checkpoint + sharding
def test_checkpoint_packed_roundtrip_elastic(tmp_path):
    """Packed 4-bit states: save -> elastic restore onto a different mesh
    must be bit-exact, with the packing recorded in the manifest."""
    from repro.train import checkpoint as C
    params = {"w": jnp.ones((64, 64)), "b": jnp.zeros((8,))}
    opt = make_optimizer("adam8", lr=1e-2, min_8bit_size=256,
                         override_32bit=lambda p: False, state_bits=(4, 8))
    st = opt.init(params)
    grad = jax.jit(jax.grad(lambda p: sum(
        jnp.sum(x ** 2) for x in jax.tree_util.tree_leaves(p))))
    p = params
    for _ in range(3):
        p, st = opt.apply(grad(p), st)
    d = str(tmp_path)
    final = C.save(d, 3, st)
    with open(os.path.join(final, "manifest.json")) as f:
        manifest = json.load(f)
    packed_entries = [e for e in manifest["index"] if "packed" in e]
    assert packed_entries and all(e["packed"]["bits"] == 4
                                  for e in packed_entries)
    # elastic restore onto an explicit (degenerate) mesh placement
    mesh = make_mesh((1, 1), ("data", "model"))
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    shardings = jax.tree_util.tree_map(lambda x: sh, st)
    st_b = C.restore(d, 3, jax.eval_shape(lambda s: s, st), shardings)
    assert isinstance(st_b.arena.codes_m, PackedCodes)
    assert isinstance(unpool_state(st_b).leaves["w"].codes_m, PackedCodes)
    for a, b in zip(jax.tree_util.tree_leaves(st),
                    jax.tree_util.tree_leaves(st_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # ...and a resumed step is identical to the uninterrupted one
    pa, sta = opt.apply(grad(p), st)
    pb, stb = opt.apply(grad(p), st_b)
    for a, b in zip(jax.tree_util.tree_leaves((pa, sta)),
                    jax.tree_util.tree_leaves((pb, stb))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_packed_bits_mismatch_rejected(tmp_path):
    from repro.train import checkpoint as C
    params = {"w": jnp.ones((64, 64))}
    opt4 = make_optimizer("adam8", lr=1e-2, min_8bit_size=256,
                          override_32bit=lambda p: False, state_bits=(4, 8))
    # 5-bit template has the same absmax/master shapes but different packed
    # widths AND different bits; both must be rejected, not reinterpreted.
    opt5 = make_optimizer("adam8", lr=1e-2, min_8bit_size=256,
                          override_32bit=lambda p: False, state_bits=(5, 8))
    st4 = opt4.init(params)
    d = str(tmp_path)
    C.save(d, 1, st4)
    with pytest.raises(ValueError):
        C.restore(d, 1, jax.eval_shape(lambda: opt5.init(params)))
    # packedness itself must agree: a packed checkpoint cannot load into a
    # plain-8-bit template (and vice versa), even where byte shapes allow
    opt8 = make_optimizer("adam8", lr=1e-2, min_8bit_size=256,
                          override_32bit=lambda p: False)
    with pytest.raises(ValueError, match="packed"):
        C.restore(d, 1, jax.eval_shape(lambda: opt8.init(params)))
    C.save(d, 2, opt8.init(params))
    with pytest.raises(ValueError, match="packed"):
        C.restore(d, 2, jax.eval_shape(lambda: opt4.init(params)))


@pytest.mark.parametrize("layout", [None, "nibble_interleaved"])
def test_checkpoint_packed_layout_mismatch_rejected(tmp_path, layout):
    """Packed bytes are only meaningful under the layout that wrote them: a
    manifest whose packed entries carry no layout tag (written before the
    tag existed) or another layout is refused, never unpacked."""
    from repro.train import checkpoint as C
    params = {"w": jnp.ones((64, 64))}
    opt = make_optimizer("adam8", lr=1e-2, min_8bit_size=256,
                         override_32bit=lambda p: False, state_bits=(4, 8))
    d = str(tmp_path)
    final = C.save(d, 1, opt.init(params))
    path = os.path.join(final, "manifest.json")
    with open(path) as f:
        manifest = json.load(f)
    packed_entries = [e for e in manifest["index"] if "packed" in e]
    assert packed_entries and all(e["packed"]["layout"] == PACK_LAYOUT
                                  for e in packed_entries)
    for e in packed_entries:
        if layout is None:
            del e["packed"]["layout"]
        else:
            e["packed"]["layout"] = layout
    with open(path, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(FormatError, match="layout"):
        C.restore(d, 1, jax.eval_shape(lambda: opt.init(params)))


def test_opt_state_shardings_packed_block_axis():
    """Sharding rules treat packed codes like plain codes: the block-count
    axis is sharded over all mesh axes, the byte axis never is."""
    from repro.sharding import rules
    mesh = make_mesh((1, 1), ("data", "model"))
    params = {"w": jnp.zeros((64, 64))}
    opt = make_optimizer("adam8", lr=1e-3, min_8bit_size=256,
                         override_32bit=lambda p: False, state_bits=(4, 8))
    st = opt.init(params)
    abstract = jax.eval_shape(lambda: opt.init(params))
    pshard = {"w": jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec())}
    shd = rules.opt_state_shardings(abstract, pshard, mesh,
                                    rules.ShardingPolicy())
    codes_shd = shd.arena.codes_m
    assert isinstance(codes_shd, PackedCodes)
    spec = codes_shd.packed.spec
    assert spec[0] == ("data", "model")
    assert len(spec) == 1 or spec[1] is None
    # structure mirrors the state: device_put works leafwise
    st_placed = jax.device_put(st, shd)
    np.testing.assert_array_equal(
        np.asarray(st_placed.arena.codes_m.packed),
        np.asarray(st.arena.codes_m.packed))
