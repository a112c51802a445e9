"""Where JAX's persistent compilation cache lives.

Every entry point that compiles for a device (the launcher, the model
server, bench.py, the test conftest) calls `configure()` before its
first compile, so the processes of one run — and the next run in the
same checkout — share what was compiled. The directory is part of the
cache key: it must not move between processes or runs.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache, beside the package; listed in .gitignore
_IN_CHECKOUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure() -> str:
    """Return the cache directory in force. Where JAX_COMPILATION_CACHE_DIR
    is set JAX reads it by itself and nothing is set here; where it is
    not, the cache goes to one fixed directory inside the checkout."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", _IN_CHECKOUT)
    return _IN_CHECKOUT
