"""Where JAX's persistent compilation cache lives.

Entry points call ``enable_compile_cache()`` from ``main()`` — never at
import — before their first compile.  The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set here.
* unset: the cache goes to ``<checkout>/.jax_cache``, a fixed path.  The
  cache directory is part of what a later run has to find again, so it is
  never derived from a temp name, a pid or the time.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Place the persistent compilation cache and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
