"""Where compiled programs persist between processes.

One rule, shared by every entry point that compiles (chip_smoke.py,
the examples, benchmark/, the test suite): when the environment names a
cache with `JAX_COMPILATION_CACHE_DIR`, jax already reads it from
there and nothing here touches the setting; otherwise the cache sits at
one fixed, git-ignored path inside the checkout. The path never holds
a temp name, pid or time, so a second process finds what the first
compiled. The autotune table (ops/autotune.py) — an input to compiled
programs — lives in the same directory.
"""

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def compile_cache_dir():
    """The directory the persistent compile cache uses (or would use,
    once `enable_compile_cache` has run)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        DEFAULT_COMPILE_CACHE_DIR


def enable_compile_cache():
    """Turn the persistent compile cache on; returns its directory.
    To move the cache, set `JAX_COMPILATION_CACHE_DIR` before the
    process starts."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)
    return compile_cache_dir()
