"""Persistent XLA compilation cache for the entry points.

A cold full-width run compiles every step shape it serves (decode, each
prefill-chunk bucket, the slot reset); on a TPU each takes seconds.  The
entry points (``serve.main``, ``train.main``, ``benchmarks/run.py`` and
``chip_smoke.py``) call ``enable_compile_cache()`` once at start-up, so a
second run of the same program finds those executables on disk.  It is
never called at import or by the library, so tests and embedding code keep
JAX's own default.
"""

from __future__ import annotations

import os

# <repo>/src/repro/launch/compile_cache.py -> <repo>/.jax_cache (gitignored).
# Fixed, never derived from a temp name, a pid or the time: the directory
# is part of what makes a later run hit.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` when it is set, else at ``.jax_cache/``
    in the checkout, and return the directory used."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
