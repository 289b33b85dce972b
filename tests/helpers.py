"""Shared tiny-train harness for end-to-end tests.

One pipeline/step setup (previously duplicated inside test_system and
needed again by the golden-trajectory and partition end-to-end tests):
build the reduced paper LM on synthetic data, jit one train step, run N
steps, optionally recording per-step metric traces.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.data.pipeline import DataConfig, SyntheticLMPipeline
from repro.train import loop as L
from repro.launch.mesh import make_mesh


def tiny_cfg(d_model=64, n_layers=2, vocab_size=128):
    return base.reduced(base.get_config("paper-lm-209m"), d_model=d_model,
                        n_layers=n_layers, vocab_size=vocab_size)


def tiny_pipe(vocab_size=128, seq_len=32, global_batch=8):
    return SyntheticLMPipeline(DataConfig(vocab_size=vocab_size,
                                          seq_len=seq_len,
                                          global_batch=global_batch))


def mesh_of(n: int, axis: str = "data"):
    """An ``(n,)`` mesh on the forced host devices, or skip."""
    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices "
                    f"(xla_force_host_platform_device_count)")
    return make_mesh((n,), (axis,))


def tiny_train(opt, steps: int, *, cfg=None, pipe=None, seed=0, trace=()):
    """Init + run ``steps`` jitted train steps.

    Returns ``(state, metrics, traces)`` where ``metrics`` is the last
    step's metric dict and ``traces`` maps each name in ``trace`` to the
    per-step list of float values — the golden-trajectory probes.
    """
    cfg = cfg or tiny_cfg()
    pipe = pipe or tiny_pipe(vocab_size=cfg.vocab_size)
    state, _ = L.init_train_state(cfg, opt, jax.random.PRNGKey(seed))
    # donated step (DESIGN.md §13c) — the loop below rebinds state
    step = L.jit_train_step(cfg, opt)
    traces = {name: [] for name in trace}
    m = {}
    for i in range(steps):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
        state, m = step(state, batch)
        for name in trace:
            traces[name].append(float(m[name]))
    return state, m, traces


def assert_trees_equal(a, b, msg=""):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), msg
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=msg)
