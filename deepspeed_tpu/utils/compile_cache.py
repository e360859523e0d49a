"""One place that decides where JAX's persistent compilation cache lives.

Compiling BERT-large's train step or a GPT-2 serving program takes minutes,
and a machine that runs one command and is thrown away pays that every time
unless the cache sits somewhere the caller controls. The cache directory is
part of JAX's cache key, so it has to be the SAME path on every run: no
temporary directory, process id or timestamp ever goes into it.

- ``JAX_COMPILATION_CACHE_DIR`` set: nothing to do. JAX reads the variable
  itself, and whoever set it owns the placement.
- unset: ``<checkout>/.jax_cache`` (git-ignored), so repeated runs in one
  checkout share their compiles.

Every entry point that compiles calls ``enable_compile_cache()`` before its
first jit: ``chip_smoke.py``, ``bench.py``'s child, the examples and the
serving replica worker.
"""

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache():
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
