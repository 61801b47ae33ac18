"""The one place that asks JAX which backend it runs on, and the one place
that places the persistent compilation cache.

Nothing here initialises a backend at import; `on_tpu` does when called.
"""
from __future__ import annotations

import os

import jax

#: <checkout>/.jax_cache — fixed, derived from the package location (the
#: directory is part of what makes a cache entry findable again, so it is
#: never a temp dir, a pid or a clock); git-ignored
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """Mosaic lowers the Pallas kernels on a TPU only; every other
    backend runs them in interpret mode."""
    return not on_tpu()


def configure_compile_cache() -> None:
    """Place JAX's persistent compilation cache, once, at package import.

    `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself and no
    directory is set in code.  Unset: `DEFAULT_CACHE_DIR`.  Either way
    every program is kept, however short its compile: one training run
    is a few large programs plus some hundred sub-second ones, which
    JAX's default 1 s floor would recompile in every process; and an
    entry is found again only by a program with the same op metadata."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # The program names its device work through op metadata
    # (jax.named_scope -> HLO op_name -> the profiler's tf_op).  JAX's
    # default cache key leaves metadata out, so an executable cached
    # before a scope was added or moved would come back with the old
    # names in every later trace; with it in the key such an entry misses.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
