"""The one rule for JAX's persistent compilation cache.

Cold compiles at serving geometry cost minutes (a 2^22 ring's fused
step alone is tens of seconds per launch shape), so the long-running
entry point (``main/example.py``) keeps compiled programs on disk. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself
and nothing is set in code; otherwise the cache lives at the FIXED path
``<checkout>/.jax_cache`` — the path is part of the cache key, so a
directory that moves (temp name, pid, home) never hits.

Tests and ``scripts/bench_smoke.py`` never call this: their
zero-recompile gates must not meet a persistent cache.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def configure() -> str:
    """Point JAX at the compile cache; call before first use of JAX.
    Returns the directory in effect."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
