"""Where the persistent XLA compilation cache lives.

A cold process recompiles everything it runs (the 124M train step
alone is the better part of a minute), and the cache directory is part
of the cache key — a directory that moves never hits. So the place is
fixed from outside the program: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (jax reads it itself; nothing is set in code), else
one fixed, git-ignored directory in the checkout. Never a temp name, a
pid or a timestamp.

Every entry point calls this once before its first jit: ``cli.main``,
``bench.main``, ``chip_smoke.py`` and ``serve.worker.run_worker``.
Tests keep the cache off (tests/conftest.py sets
``JAX_ENABLE_COMPILATION_CACHE=false``), which this helper leaves alone.
"""

from __future__ import annotations

import os

#: <checkout>/.jax_cache — the fixed in-repo default (.gitignore lists it)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
