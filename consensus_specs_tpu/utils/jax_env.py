"""Shared JAX environment setup: the persistent compile cache.

Every entry point (pytest, bench.py, chip_smoke.py, __graft_entry__,
plain consumer imports) uses one cache directory so the big XLA
programs (pairing, hash-to-curve, MSM) compile once per machine.

Where it lives is decided outside the code: ``JAX_COMPILATION_CACHE_DIR``
when it is set (JAX reads it itself; nothing here overrides it),
otherwise the fixed ``.jax_cache/`` at the checkout root.  The path is
part of nothing but the cache's location — JAX keys each entry by the
program, the backend and the compiler version — so a fixed path is what
lets a later process find what an earlier one wrote.

The platform is whatever JAX says: ``JAX_PLATFORMS=cpu`` is how tests
and tools ask for the host CPU, and nothing in this package picks a
platform on its own.
"""
import os

CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def cache_dir() -> str:
    """The persistent compile-cache directory this process uses."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_ROOT


def setup_compile_cache() -> str:
    """Point JAX at :func:`cache_dir`; idempotent.

    Works both before and after ``import jax`` (config reads the env var
    lazily until a backend is initialized; after that it is pushed
    through jax.config as well, which is safe pre-first-compile).
    """
    path = cache_dir()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "2")
    import sys
    if "jax" in sys.modules:
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]))
    return path
