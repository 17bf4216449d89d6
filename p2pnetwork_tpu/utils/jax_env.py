"""JAX process set-up shared by the repo's entry points.

``apply_platform_env`` re-applies ``JAX_PLATFORMS`` through ``jax.config``:
JAX reads the variable once, when it is first imported, so a process that
imported jax before setting it (a pytest plugin, say) would ignore it.

``enable_compile_cache`` places JAX's persistent compilation cache. The
entry points (``chip_smoke.py``, ``bench.py``, ``benchmarks/ladder.py``)
call it; the package never does on import, and the tests never do.
"""

from __future__ import annotations

import os

#: The cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed
#: path in the checkout (the path is part of the cache key, so it must not
#: move between runs), listed in ``.gitignore``.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def apply_platform_env() -> None:
    """Honor ``JAX_PLATFORMS`` from the environment via jax.config.

    No-op when the variable is unset or the backend is already initialized.
    """
    platforms = os.environ.get("JAX_PLATFORMS")
    if not platforms:
        return
    try:
        import jax

        jax.config.update("jax_platforms", platforms)
    except (ImportError, RuntimeError):
        pass


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
