"""Where the chip-owning process keeps JAX's persistent compile cache.

A cold TPU process compiles for tens of seconds (the train cell's
``setup_s`` reads 23 s warm and 65 s cold: ledger, PR 24); the cache
directory is part of the cache key's lookup, so it must not move
between runs. Called where
a process first touches JAX for the device: ``ray_tpu.init()``'s TPU
detection and ``chip_smoke.py``.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Returns the cache directory in effect. With
    ``JAX_COMPILATION_CACHE_DIR`` set, jax has already read it and
    nothing is set in code; otherwise the cache lives at the fixed
    path ``<checkout>/.jax_cache`` (git-ignored)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
