"""Where JAX's persistent compilation cache lives.

Every entry point (``launch/train.py``, ``launch/serve.py``,
``benchmarks/run.py``, ``chip_smoke.py``) calls :func:`enable_compile_cache`
before it compiles anything, so a second run on the same machine reuses
the first one's executables.
"""
from __future__ import annotations

import pathlib

import jax

# <checkout>/.jax_cache: src/repro/launch/ -> the checkout root.  A fixed
# path, never built from a temp name, pid or time: the directory must be
# the same on the next run for the cache to hit.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it into
    ``jax_compilation_cache_dir`` and the cache lives there: this sets no
    other directory.  Otherwise the cache goes to ``<checkout>/.jax_cache``
    (listed in ``.gitignore``)."""
    configured = jax.config.jax_compilation_cache_dir
    if configured:
        return configured
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
