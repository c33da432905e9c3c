"""Where compiled programs persist between processes.

JAX's persistent compilation cache lets a process reuse what an earlier
process compiled.  A cold start on the chip otherwise compiles every
kernel and every jitted chain again.  The cache directory is part of each
entry's key, so it must not move from one run to the next.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache, resolved from this file (src/repro/launch/...)
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps the cache there
    already and nothing is changed.  Otherwise the cache goes to the
    fixed ``<checkout>/.jax_cache``.  Call it at the start of a
    launcher's ``main()`` — never at import, and never from tests.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
