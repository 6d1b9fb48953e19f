"""Where the persistent XLA compile cache lives.

Every entry point that compiles (``server.serve.main``, ``chip_smoke.py``,
``chipbench/run.py``) calls :func:`enable_compile_cache` once, before its
first compilation. The directory is part of the cache key's lookup, so it
must not move between runs: it is either the one the deployment names or
one fixed path in the checkout — never built from a temp name, a pid or
the time.
"""

from __future__ import annotations

import os

#: JAX reads this variable itself (it backs ``jax_compilation_cache_dir``).
#: A placement, like an address or a port — not a feature switch.
ENV = "JAX_COMPILATION_CACHE_DIR"

#: the fixed in-checkout default (git-ignored)
DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_compile_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the program sets no path in
    code — JAX already honours the variable; otherwise the cache goes to
    :data:`DEFAULT_DIR`."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def cache_entries(path: str) -> int:
    """Number of files in the cache directory (0 when it does not exist) —
    what an entry point prints before and after to say whether it ran
    warm."""
    try:
        return len(os.listdir(path))
    except FileNotFoundError:
        return 0
