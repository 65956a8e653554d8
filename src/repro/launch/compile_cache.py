"""JAX's persistent compilation cache at a fixed place.

The cache directory is part of what makes an entry found again, so it never
moves between runs: ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets
it (JAX reads that variable itself, and nothing here overrides it), else
``<checkout>/.jax_cache`` (git-ignored). Entry points call
``enable_compile_cache()``; importing this module changes nothing.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        os.pardir, os.pardir, os.pardir))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def compile_cache_dir() -> str:
    """The environment's cache directory, else the fixed in-checkout one."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    and return that directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
