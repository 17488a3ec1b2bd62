"""Where JAX's persistent compilation cache lives.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, the cache goes there and
nowhere else. Otherwise the cache goes to one fixed path inside the
checkout, ``<repo>/.jax_cache`` (listed in ``.gitignore``): the directory
is part of what a later run must find again, so it never carries a
temporary name, a pid or a time. Entry points (``chip_smoke.py``, the
benchmarks) call `configure_compile_cache` once, before their first
compile; importing this module changes nothing.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
