"""Where the persistent XLA compilation cache lives.

One rule, for every entry point that wants compiled programs to survive the
process (``chip_smoke.py``, ``benchmark/run.py``):

- ``JAX_COMPILATION_CACHE_DIR`` set: do nothing. JAX reads the variable
  itself, and no code path here sets another directory — whoever runs the
  program (a CI driver, a chip host that keeps its cache between calls)
  owns the placement.
- unset: ``<checkout>/.jax_cache``, derived from this file's location. The
  directory is part of the cache key, so it must never move between runs:
  no tempfile, pid or timestamp in it. ``.jax_cache/`` is git-ignored.
"""

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Apply the rule above; returns the directory in effect."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
