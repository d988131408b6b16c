"""Where JAX's persistent compilation cache lives — decided in one place.

A cold compile of a train step or a serving program family costs minutes of
chip time; the persistent cache turns the second run's into disk reads.  The
cache directory is part of the cache key, so it must be the same path on
every run: never a temp name, a pid or a time.

Every entry point that compiles (``chip_smoke.py``,
``tools/fleet_bench.py`` and the two launchers)
calls :func:`configure_compile_cache` before its first compile, and nothing
else in the tree names the cache-directory option.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR_OPTION = "jax_compilation_cache_dir"

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.realpath(__file__))))
# fixed and normalised: <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def configure_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache:
    JAX reads the variable itself, and no directory is set in code (a value
    set in code would override what the machine's owner asked for).
    Otherwise the cache is ``<checkout>/.jax_cache``.  A cache directory
    that cannot be created raises — a run that quietly compiles cold every
    time is a fault, not a fallback."""
    import jax

    path = os.environ.get(ENV_VAR)
    if path:
        os.makedirs(path, exist_ok=True)
    else:
        path = DEFAULT_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update(CACHE_DIR_OPTION, path)
    # cache every program, however small or quick to compile: a serving
    # engine's family is dozens of sub-second programs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
