"""Where compiled programs are kept between runs.

JAX's persistent compilation cache keys each entry on the cache path too,
so the path must not move from one run to the next: it is either the
directory ``JAX_COMPILATION_CACHE_DIR`` names, or ``.jax_cache/`` at the
root of this checkout. Entry points call :func:`use_compile_cache` before
their first compile; importing this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` — this file is ``<checkout>/src/repro/launch/``
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Turn on the persistent compilation cache. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
    is changed; otherwise the cache goes to :data:`CHECKOUT_CACHE_DIR`."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
