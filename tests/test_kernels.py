"""Pallas kernel validation: interpret-mode execution vs the pure-jnp oracle
(ref.py), swept over shapes and dtypes as required for every kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core import qmap
from repro.kernels import common, ops, ref

QS = jnp.asarray(qmap.get_qmap("dynamic", True))
QU = jnp.asarray(qmap.get_qmap("dynamic", False))

SHAPES = [(1, 128), (4, 256), (8, 512), (3, 2048), (16, 1024)]


def _rand(nb, bsz, seed=0, scale=1.0):
    k = jax.random.PRNGKey(seed)
    return jax.random.normal(k, (nb, bsz), jnp.float32) * scale


@pytest.mark.parametrize("nb,bsz", SHAPES)
def test_quantize_kernel_matches_ref(nb, bsz):
    x = _rand(nb, bsz, scale=0.01)
    c_k, a_k = ops.quantize_blockwise(x, QS, impl="interpret")
    c_r, a_r = ref.quantize_ref(x, QS)
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))
    np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r))


@pytest.mark.parametrize("nb,bsz", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dequantize_kernel_matches_ref(nb, bsz, dtype):
    x = _rand(nb, bsz, seed=1)
    c, a = ref.quantize_ref(x, QS)
    d_k = ops.dequantize_blockwise(c, a, QS, impl="interpret", dtype=dtype)
    d_r = ref.dequantize_ref(c, a, QS, dtype)
    np.testing.assert_allclose(np.asarray(d_k, np.float32),
                               np.asarray(d_r, np.float32), atol=1e-6)


ALGOS = list(ops.ALGOS)
HYPER = dict(lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
             weight_decay=0.01, step=7.0, trust_coeff=1e-3)


def _fused_inputs(algo, nb, bsz, gdtype=jnp.float32):
    """(p, g, codes_m, absmax_m, codes_r, absmax_r, qmap_m, qmap_r)."""
    spec_two = algo in ("adam", "adamw", "lamb")
    p = _rand(nb, bsz, 2)
    g = _rand(nb, bsz, 3, 0.1).astype(gdtype)
    if algo == "adagrad":
        cm, am = ref.quantize_ref(jnp.abs(_rand(nb, bsz, 4, 1e-3)), QU)
        q1 = QU
    else:
        cm, am = ref.quantize_ref(_rand(nb, bsz, 4, 0.01), QS)
        q1 = QS
    cr = ar = None
    if spec_two:
        cr, ar = ref.quantize_ref(jnp.abs(_rand(nb, bsz, 5, 1e-4)), QU)
    return p, g, cm, am, cr, ar, q1, QU


def _assert_results_close(out_k, out_r, tol_codes=0.001):
    for name, k_, r_ in zip(out_k._fields, out_k, out_r):
        if k_ is None:
            assert r_ is None, name
        elif k_.dtype == jnp.uint8:
            # codes may differ only at exact boundary ties
            mism = int((np.asarray(k_) != np.asarray(r_)).sum())
            assert mism <= k_.size * tol_codes, (name, mism)
        else:
            np.testing.assert_allclose(np.asarray(k_, np.float32),
                                       np.asarray(r_, np.float32),
                                       atol=5e-6, rtol=1e-5, err_msg=name)


@pytest.mark.parametrize("algo", ALGOS)
@pytest.mark.parametrize("nb,bsz", [(2, 256), (4, 512)])
def test_fused_update_matches_ref(algo, nb, bsz):
    """The unified kernel path (interpret) vs the jnp registry entry, for
    all six algorithms — including the LAMB/LARS norm prologue."""
    args = _fused_inputs(algo, nb, bsz)
    out_k = ops.fused_update(algo, *args, impl="interpret", **HYPER)
    out_r = ops.fused_update(algo, *args, impl="jnp", **HYPER)
    _assert_results_close(out_k, out_r)


@pytest.mark.parametrize("gdtype", [jnp.float32, jnp.bfloat16])
def test_fused_update_grad_dtypes(gdtype):
    args = _fused_inputs("adam", 8, 2048, gdtype)
    out_k = ops.fused_update("adam", *args, impl="interpret", **HYPER)
    out_r = ops.fused_update("adam", *args, impl="jnp", **HYPER)
    _assert_results_close(out_k, out_r)


@pytest.mark.parametrize("algo", ["adam", "lars"])
def test_fused_update_stochastic_parity(algo):
    """In-kernel stochastic rounding uses the same counter-based PRNG as
    the jnp reference, so codes agree bit-for-bit given the same seed."""
    args = _fused_inputs(algo, 2, 256)
    out_k = ops.fused_update(algo, *args, impl="interpret",
                             stochastic=True, seed=123, **HYPER)
    out_r = ops.fused_update(algo, *args, impl="jnp",
                             stochastic=True, seed=123, **HYPER)
    _assert_results_close(out_k, out_r)
    # ...and a different seed actually changes the rounding
    out_s = ops.fused_update(algo, *args, impl="jnp",
                             stochastic=True, seed=124, **HYPER)
    assert int((np.asarray(out_r.codes_m) != np.asarray(out_s.codes_m)).sum()) > 0


def test_fused_update_stochastic_mean_preserving():
    """Averaged over seeds, stochastic requantization of the new state is
    closer to the exact 32-bit state than deterministic rounding (the whole
    point of the ablation, paper App H)."""
    nb, bsz = 1, 2048
    qs = QS
    p = jnp.zeros((nb, bsz))
    # With zero-initialized momentum, m2 == g exactly. One 1.0 element pins
    # the block absmax, the 0.3 bulk sits between dynamic-map codes.
    g = jnp.full((nb, bsz), 0.3).at[0, 0].set(1.0)
    cm, am = ref.quantize_ref(jnp.zeros((nb, bsz)), qs)
    kw = dict(HYPER, lr=0.0, weight_decay=0.0)
    exact = float(g.mean())
    det = ops.fused_update("momentum", p, g, cm, am, None, None, qs, QU,
                           impl="jnp", **kw)
    det_mean = float(ref.dequantize_ref(det.codes_m, det.absmax_m, qs).mean())
    assert abs(det_mean - exact) > 1e-6   # deterministic rounding is biased
    means = []
    for seed in range(30):
        st = ops.fused_update("momentum", p, g, cm, am, None, None, qs, QU,
                              impl="jnp", stochastic=True, seed=seed, **kw)
        means.append(float(ref.dequantize_ref(st.codes_m, st.absmax_m, qs).mean()))
    assert abs(np.mean(means) - exact) < abs(det_mean - exact)


def test_fused_update_gnorm_scale_scales_grad():
    """gnorm_scale=0.5 inside the fused path must equal feeding g/2."""
    args = _fused_inputs("adam", 2, 256)
    p, g, cm, am, cr, ar, q1, q2 = args
    a = ops.fused_update("adam", p, g, cm, am, cr, ar, q1, q2,
                         impl="interpret", gnorm_scale=0.5, **HYPER)
    b = ops.fused_update("adam", p, g * 0.5, cm, am, cr, ar, q1, q2,
                         impl="interpret", **HYPER)
    _assert_results_close(a, b)


def test_fused_update_tensorwise_ablation():
    """blockwise=False (tensor-wise absmax) routes to the jnp entry and
    produces a single shared absmax per state tensor."""
    args = _fused_inputs("adam", 4, 256)
    out = ops.fused_update("adam", *args, impl="interpret",
                           blockwise=False, **HYPER)
    am = np.asarray(out.absmax_m)
    assert np.all(am == am[0])


def test_fused_update_unknown_combo_raises():
    args = _fused_inputs("adam", 2, 256)
    with pytest.raises(KeyError):
        ops.fused_update("adam", *args, impl="cuda", **HYPER)


def test_fused_update_row_padding():
    """n_blocks not a multiple of DEFAULT_ROWS is padded transparently."""
    args = _fused_inputs("adam", 5, 256)
    out_k = ops.fused_update("adam", *args, impl="interpret", **HYPER)
    out_r = ops.fused_update("adam", *args, impl="jnp", **HYPER)
    assert out_k.p.shape == (5, 256)
    _assert_results_close(out_k, out_r)


def test_kernel_row_padding():
    """ops.* pads non-multiple-of-rows block counts transparently."""
    x = _rand(5, 256)      # 5 rows, default rows=8 -> padded to 8
    c_k, a_k = ops.quantize_blockwise(x, QS, impl="interpret", rows=8)
    c_r, a_r = ref.quantize_ref(x, QS)
    assert c_k.shape == (5, 256)
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))


def test_default_rows_consistent():
    """One DEFAULT_ROWS across the kernel package (hoisted into common)."""
    from repro.kernels import blockwise_dequant, blockwise_quant, common
    assert ops.DEFAULT_ROWS == common.DEFAULT_ROWS
    assert blockwise_quant.DEFAULT_ROWS == common.DEFAULT_ROWS
    assert blockwise_dequant.DEFAULT_ROWS == common.DEFAULT_ROWS


def test_zero_block_safe():
    """All-zero blocks (padding) must not produce NaN (absmax=0 guard)."""
    x = jnp.zeros((4, 256))
    c, a = ops.quantize_blockwise(x, QS, impl="interpret")
    d = ops.dequantize_blockwise(c, a, QS, impl="interpret")
    assert not bool(jnp.isnan(d).any())
    assert float(jnp.abs(d).max()) == 0.0


def test_quantize_other_codebooks():
    """Kernel works for any sorted 256-codebook (linear, quantile...)."""
    for name, signed in [("linear", True), ("quantile_normal", True),
                         ("inverse_dynamic", False)]:
        cb = jnp.asarray(qmap.get_qmap(name, signed))
        x = _rand(4, 256, 9) if signed else jnp.abs(_rand(4, 256, 9))
        c_k, a_k = ops.quantize_blockwise(x, cb, impl="interpret")
        c_r, a_r = ref.quantize_ref(x, cb)
        np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))


# ------------------------------------------- lookup exactness (common.py)
# Every map the kernels quantize with, at 8 bits and at the k-bit widths.
LOOKUP_MAPS = [(name, signed, bits)
               for name, signed in [("dynamic", True), ("dynamic", False),
                                    ("linear", True), ("quantile_normal", True)]
               for bits in (8, 4, 5, 6)]


def _lookup_inputs(cb: np.ndarray):
    """Every boundary with its f32 neighbours on each side, every code
    value, +-0, +-inf, NaN and values past +-1, as a (4, N) f32 tile."""
    b = qmap.boundaries(cb)
    vals = np.concatenate([
        b, np.nextafter(b, np.float32(-np.inf)),
        np.nextafter(b, np.float32(np.inf)), cb,
        np.asarray([0.0, -0.0, np.inf, -np.inf, np.nan, 1.5, -1.5],
                   np.float32)]).astype(np.float32)
    n = -(-vals.size // (4 * 128)) * 128
    x = np.full((4 * n,), np.nan, np.float32)
    x[:vals.size] = vals
    return x.reshape(4, n), b


def _in_kernel(fn, x, table):
    """Run ``fn(x, table_ref)`` inside an interpret-mode Pallas call with
    the table as its VMEM lane-table operand."""
    def kernel(x_ref, t_ref, o_ref):
        o_ref[...] = fn(x_ref[...], t_ref)

    out_dtype = jnp.int32 if x.dtype == jnp.float32 else jnp.float32
    return pl.pallas_call(
        kernel, grid=(1,),
        in_specs=[pl.BlockSpec(x.shape, lambda i: (0, 0)),
                  common.table_spec()],
        out_specs=pl.BlockSpec(x.shape, lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, out_dtype),
        interpret=True)(x, table)


@pytest.mark.parametrize("impl", ["interpret", "jnp"])
@pytest.mark.parametrize("name,signed,bits", LOOKUP_MAPS)
def test_lookup_is_exact(name, signed, bits, impl):
    """``common.encode`` is ``sum_j [x >= b_j]`` and ``common.decode`` is
    ``qmap[code]``, bit for bit, at and beside every boundary, on every
    code and on the special values — inside a kernel and outside."""
    cb = qmap.get_qmap(name, signed, bits=bits)
    levels = 1 << bits
    x, b = _lookup_inputs(cb)
    codes = (np.arange(x.size) % levels).astype(np.int32).reshape(x.shape)
    bounds, table = common.padded_bounds(cb), common.padded_qmap(cb)

    def enc(v, t):
        return common.encode(v, t, levels)

    def dec(c, t):
        return common.decode(c, t, levels)

    if impl == "interpret":
        got_codes = _in_kernel(enc, jnp.asarray(x), bounds)
        got_vals = _in_kernel(dec, jnp.asarray(codes), table)
    else:
        got_codes = enc(jnp.asarray(x), bounds)
        got_vals = dec(jnp.asarray(codes), table)
    # The sum in XLA too: it treats an f32 subnormal as the kernels do.
    want_codes = jnp.sum(jnp.asarray(x)[..., None] >= jnp.asarray(b),
                         axis=-1)
    np.testing.assert_array_equal(np.asarray(got_codes), want_codes)
    np.testing.assert_array_equal(np.asarray(got_vals).view(np.uint32),
                                  cb[codes].view(np.uint32))
