"""Where compiled programs and tuned block shapes persist — ONE rule.

The directory is part of jax's cache key, so a cache that moves never
hits. The rule, for every entry point that compiles on the chip
(chip_smoke.py, bench.py, tools/profile_*.py) and for the flash tune
cache (ops/pallas_attention.py):

  * ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and this code
    sets nothing — whoever runs the program placed the cache.
  * unset: ``<checkout>/.jax_cache`` (git-ignored), fixed.

`frameless_locations` is for the other half of a hit: a key that does not
follow the process's trace history.
"""
from __future__ import annotations

import contextlib
import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir() -> str:
    """The directory the rule above names; touches neither jax nor disk."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn jax's persistent compilation cache on at `cache_dir()` and
    return the directory. Call before the first compile."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    # short compiles are cached too: a cold process on a new machine pays
    # every one of them again otherwise
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return cache_dir()


@contextlib.contextmanager
def frameless_locations():
    """Lower inside this context a program whose persistent-cache key must
    not depend on the process's trace history.

    jax's key strips a program's locations but not a Pallas kernel's
    serialized body, whose operations carry the Python traceback of
    whoever first traced them (or a shared jitted helper) in the process:
    lowered after another trace history, the same program keys the cache
    anew, and a fresh process misses. Inside, locations name no frames
    (op names keep their scopes): one program, one key."""
    import jax

    was = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        yield
    finally:
        jax.config.update("jax_traceback_in_locations_limit", was)
