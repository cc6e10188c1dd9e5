"""Persistent XLA compilation-cache setup shared by the repo entry points.

First compile of the full B3+transformer train step costs minutes; the
on-disk cache makes every later process start in seconds. Called by the
trainer, server, eval, `bench.py`, `chip_smoke.py` and
`__graft_entry__.py`, and available to user scripts.

Where the cache lives: `JAX_COMPILATION_CACHE_DIR`, if set, is read by JAX
itself and this module sets no directory; otherwise a fixed path inside
the checkout. The path is part of the cache key, so it never moves.
"""

from __future__ import annotations

import os

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable_persistent_cache() -> None:
    """Turn on JAX's persistent compilation cache (see module docstring)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
