"""Where compiled programs are kept between processes.

One rule, shared by serve, train, the benchmark and ``chip_smoke.py``:

* ``JAX_COMPILATION_CACHE_DIR`` set — jax already honours it; nothing here
  sets a directory.  A deployment (or ``mxtpu-supervise --compile-cache``)
  places the cache from outside by exporting that one variable.
* unset — a FIXED directory inside the checkout, ``<repo>/.jax_cache``
  (git-ignored).  The path is part of jax's cache key, so a directory that
  moves (a pid, a timestamp, a fresh ``/tmp``) never hits.

Either way the entry-size and compile-time floors drop to zero: a serving
engine is many small programs (one per bucket), exactly the population
jax's default floors would skip.
"""
from __future__ import annotations

import os

__all__ = ["DEFAULT_DIR", "ensure_compile_cache"]

#: the in-checkout default: next to the package, never under /tmp
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def ensure_compile_cache() -> str:
    """Apply the rule above and return the active directory.  Idempotent
    and cheap; call it before the first compile of anything worth keeping
    (entry points call it first thing, engine and trainer constructors
    call it again as the backstop for library use).  Configuring after an
    earlier compile is fine — jax builds the cache lazily at the first
    compile that finds a directory set."""
    import jax
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed and not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed or jax.config.jax_compilation_cache_dir
