"""Fault tolerance: atomic checkpointing, bit-exact restart, pruning,
elastic restore (different sharding target)."""
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.core.optim import make_optimizer
from repro.data.pipeline import DataConfig, SyntheticLMPipeline
from repro.train import checkpoint as C
from repro.train import loop as L
from repro.launch.mesh import make_mesh


@pytest.fixture
def setup(tmp_path):
    cfg = base.reduced(base.get_config("paper-lm-209m"), d_model=32,
                       n_layers=2, vocab_size=64)
    pipe = SyntheticLMPipeline(DataConfig(vocab_size=64, seq_len=16,
                                          global_batch=4))
    opt = make_optimizer("adam8", lr=5e-3, min_8bit_size=256)
    state, _ = L.init_train_state(cfg, opt, jax.random.PRNGKey(0))
    step = jax.jit(L.make_train_step(cfg, opt))
    return cfg, pipe, opt, state, step, str(tmp_path)


def _run(step, pipe, state, lo, hi):
    for i in range(lo, hi):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
        state, _ = step(state, batch)
    return state


def test_restart_equivalence_bit_exact(setup):
    cfg, pipe, opt, state, step, d = setup
    state = _run(step, pipe, state, 0, 5)
    C.save(d, 5, state)
    final_a = _run(step, pipe, state, 5, 9)
    template = jax.eval_shape(lambda s: s, state)
    state_b = C.restore(d, 5, template)
    final_b = _run(step, pipe, state_b, 5, 9)
    for a, b in zip(jax.tree_util.tree_leaves(final_a),
                    jax.tree_util.tree_leaves(final_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_keep_last_pruning(setup):
    _, _, _, state, _, d = setup
    for s in [1, 2, 3, 4, 5]:
        C.save(d, s, state, keep_last=2)
    assert C.all_steps(d) == [4, 5]
    assert C.latest_step(d) == 5


def test_atomic_no_partial_dirs(setup):
    _, _, _, state, _, d = setup
    C.save(d, 7, state)
    leftovers = [f for f in os.listdir(d) if f.startswith(".tmp_")]
    assert leftovers == []


def test_shape_mismatch_rejected(setup):
    _, _, _, state, _, d = setup
    C.save(d, 1, state)
    bad = jax.eval_shape(
        lambda: jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape + (2,), x.dtype)
            if hasattr(x, "shape") and x.ndim > 0 else x,
            state))
    with pytest.raises((ValueError, KeyError)):
        C.restore(d, 1, bad)


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 1), (4, 1), (2, 2)])
def test_elastic_restore_new_sharding(setup, mesh_shape):
    """Checkpoints hold full logical arrays -> restoring with different
    device placement ({1,2,4}-device meshes here; 512-dev in the dryrun)
    must be value-identical."""
    _, _, _, state, _, d = setup
    n = mesh_shape[0] * mesh_shape[1]
    if jax.device_count() < n:
        pytest.skip("needs more forced host devices")
    C.save(d, 3, state)
    mesh = make_mesh(mesh_shape, ("data", "model"))
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    shardings = jax.tree_util.tree_map(lambda x: sh, state)
    state_b = C.restore(d, 3, jax.eval_shape(lambda s: s, state), shardings)
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(state_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_percentile_clipping_state_roundtrip(tmp_path):
    """The gnorm history (OptState.gnorm_vec) is ordinary state: it must
    survive save/restore bit-exactly, and a restored run must continue
    identically to the uninterrupted one."""
    d = str(tmp_path)
    params = {"w": jnp.ones((64, 64)), "b": jnp.zeros((8,))}
    opt = make_optimizer("adam8", lr=1e-2, min_8bit_size=256,
                         percentile_clipping=50, pclip_history=4,
                         override_32bit=lambda p: False)
    st = opt.init(params)
    grad = jax.jit(jax.grad(lambda p: sum(
        jnp.sum(x ** 2) for x in jax.tree_util.tree_leaves(p))))
    p = params
    for _ in range(5):
        p, st = opt.apply(grad(p), st)
    assert float(jnp.min(st.gnorm_vec)) > 0.0
    C.save(d, 5, st)
    st_b = C.restore(d, 5, jax.eval_shape(lambda s: s, st))
    np.testing.assert_array_equal(np.asarray(st.gnorm_vec),
                                  np.asarray(st_b.gnorm_vec))
    pa, sta = opt.apply(grad(p), st)
    pb, stb = opt.apply(grad(p), st_b)
    for a, b in zip(jax.tree_util.tree_leaves((pa, sta)),
                    jax.tree_util.tree_leaves((pb, stb))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_size_reflects_8bit_states(setup, tmp_path):
    """8-bit checkpoints are much smaller than 32-bit-state checkpoints.
    (The full opt_state is saved: under the pooled dispatch the quantized
    codes live in the arena, which `save` slices back per leaf.)"""
    cfg, _, _, state8, _, d = setup
    opt32 = make_optimizer("adam32", lr=5e-3)
    state32, _ = L.init_train_state(cfg, opt32, jax.random.PRNGKey(0))
    p8 = C.save(os.path.join(d, "c8"), 1, state8.opt_state)
    p32 = C.save(os.path.join(d, "c32"), 1, state32.opt_state)
    s8 = os.path.getsize(os.path.join(p8, "leaves.npz"))
    s32 = os.path.getsize(os.path.join(p32, "leaves.npz"))
    assert s8 < s32 * 0.62    # master f32 shared; stats are 8x smaller
