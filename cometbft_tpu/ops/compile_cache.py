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

One compile for the mesh: JAX's cache key holds the compile options, and
with them the id of the device a one-device program was compiled for, so
each chip of a mesh would compile (and store) its own copy of every shard
program: four chips, four compiles of a minute (PR 22). arm() keys a
program of one replica and one partition without that id (JAX itself does
so on GPUs): the first chip compiles, every other chip loads the entry
with its own device assignment. On device 0 the key is what it always was.
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
    _key_one_device_programs_without_the_device()
    return placed or default_dir()


def _key_one_device_programs_without_the_device() -> None:
    """Wrap the step of JAX's cache key that hashes the compile options
    (jax._src.cache_key, private: where a later JAX has moved it, nothing
    is wrapped and every chip compiles for itself, as before)."""
    try:
        from jax._src import cache_key
    except ImportError:
        return
    inner = getattr(cache_key, "_hash_serialized_compile_options", None)
    if inner is None or getattr(inner, "one_device_wrapped", False):
        return

    def hash_options(hash_obj, compile_options_obj,
                     strip_device_assignment=False):
        assignment = compile_options_obj.device_assignment
        one_device = bool(assignment) and (
            assignment.replica_count()
            * assignment.computation_count() == 1)
        return inner(hash_obj, compile_options_obj,
                     strip_device_assignment=(strip_device_assignment
                                              or one_device))

    hash_options.one_device_wrapped = True
    cache_key._hash_serialized_compile_options = hash_options
