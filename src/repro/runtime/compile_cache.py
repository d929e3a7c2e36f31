"""JAX's persistent compilation cache, placed from outside the program.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
:func:`enable` sets nothing: whoever runs the program decides where
compiled executables persist.  Where it is not set, the cache goes to one
fixed, git-ignored directory inside the checkout (:data:`REPO_CACHE_DIR`).
The path is fixed on purpose -- never built from a temporary name, a
process id or the time -- because a later process finds an entry only
under the same directory.

Entry points call :func:`enable` (``chip_smoke.py``, ``launch/serve.py``
and ``benchmarks/run.py``); importing this module changes nothing.
"""

from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set (left to JAX), else
    :data:`REPO_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
