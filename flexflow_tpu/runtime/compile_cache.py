"""Where jax keeps its persistent compilation cache.

Every chip call starts cold, and the train step alone compiles for
tens of seconds, so each entry script calls ``place_compile_cache``
first thing.  The directory is part of nothing the program computes,
but it has to be the SAME directory run after run for an entry to be
found again: a fixed path inside the checkout, unless the machine says
otherwise through ``JAX_COMPILATION_CACHE_DIR``.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Point jax's persistent compilation cache at a directory and
    return it.  With ``JAX_COMPILATION_CACHE_DIR`` set nothing is
    touched — jax reads the variable itself; otherwise the cache is
    ``<checkout>/.jax_cache`` (gitignored).  Call before the first
    compile."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
