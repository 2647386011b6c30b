"""Where JAX's persistent compilation cache lives.

Entry points (`chip_smoke.py`, `examples/*.py`, `repro.launch.serve`,
`benchmarks/run.py`) call `configure()` once before their first compile.
"""

from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache: a fixed path (the path is part of the cache key, so
# a directory that moves never hits), listed in .gitignore.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def configure() -> str:
    """Point `jax_compilation_cache_dir` at ``JAX_COMPILATION_CACHE_DIR`` when
    that is set, else at `DEFAULT_DIR`; returns the directory.  Sets no
    other cache option."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
