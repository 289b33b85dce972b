"""CPU tests of the benchmark.  Run them by path (the repository's own
suite collects ``tests/`` only):

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
import os
import sys
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

# nothing the CPU compiles goes into the checkout's compile cache
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="session")
def tiny_bench(tmp_path_factory):
    from chipbench.tests import tiny
    root = tmp_path_factory.mktemp("bench")
    return root, tiny.make(root)


@pytest.fixture(autouse=True)
def _interpret_kernels(monkeypatch):
    """Off the chip the Pallas kernels run in interpret mode."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "default_impl", lambda: "interpret")
