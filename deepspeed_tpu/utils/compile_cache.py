"""Where JAX's persistent compilation cache lives — placed from OUTSIDE.

Called by the measurement entry points (``chip_smoke.py``, the collective
sweep's main) — never at package import and never in tests.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself reads it and this
sets nothing. Where it is not, the cache goes to ONE fixed directory inside
the checkout: the directory is part of the cache key, so a temp name, a pid
or a time in it would never hit.
"""

from __future__ import annotations

import os

#: <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Point the persistent cache at its directory, print and return it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    print(f"compile cache: {path}", flush=True)
    return path
