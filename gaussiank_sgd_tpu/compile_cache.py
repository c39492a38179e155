"""Persistent XLA compilation cache placement — the ONE copy.

Every entry point that compiles the step programs (``train.py``,
``chip_smoke.py``, ``tests/conftest.py``, the analysis scripts) calls
:func:`enable_compile_cache` before its first jit. The directory is a deployment setting with one knob, JAX's own:

* ``JAX_COMPILATION_CACHE_DIR`` set — jax already reads it into
  ``jax_compilation_cache_dir`` at import; this module sets no directory
  in code, so whoever runs the program decides where compiled programs
  live (and whether they survive the machine);
* unset — ``<checkout>/.jax_cache`` (git-ignored). A fixed path inside
  the checkout: processes started from the same tree share it, and it
  travels with a copy of the tree.

This module imports nothing heavy at module scope (importable before jax
initializes a backend).
"""

from __future__ import annotations

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get(CACHE_DIR_ENV)
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # the step programs take seconds to minutes; 0.3 s also keeps the
    # test suite's many small repeated programs (jax's default is 1 s)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
