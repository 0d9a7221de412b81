"""JAX's persistent compilation cache for the entry points.

``python -m repro.api``, ``benchmarks/run.py`` and ``chip_smoke.py`` call
:func:`enable_compile_cache` before they compile anything, so a second run
of the same programs loads them instead of compiling again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, that is JAX's own setting and
nothing here changes it. Otherwise the cache lives in ``<repo>/.jax_cache``
(listed in ``.gitignore``). The path is fixed on purpose: a cache directory
that moves between runs never hits.
"""

from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..")
)
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
