"""The one place that decides where JAX's persistent compilation cache
lives.

Cold compiles are the expensive part of a device start: each XLA ladder
rung is about a minute on a v5e, each Pallas bucket about half of one, and
a multi-chip mesh instantiates one executable per chip. The cache
directory is part of the cache key, so it must be a FIXED path:

  JAX_COMPILATION_CACHE_DIR set   JAX reads it itself; nothing is set in
                                  code, so whoever launches the process
                                  (an operator, the chip tool) places the
                                  cache
  unset                           <checkout>/.jax_cache (git-ignored)

Never a temp name, pid or time in the path. Every entry point that wants
the cache (node boot, bench.py, the tests' conftest, chip_smoke.py) calls
arm(); no other code updates `jax_compilation_cache_dir`.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def default_dir() -> str:
    return os.path.join(_CHECKOUT, ".jax_cache")


def arm() -> str:
    """Switch the persistent compilation cache on and return the
    directory in force. Raises what jax raises: a cache that cannot be
    armed is the caller's to report, not to swallow."""
    import jax

    placed = os.environ.get(ENV_VAR)
    if not placed:
        jax.config.update("jax_compilation_cache_dir", default_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2)
    return placed or default_dir()
