"""Persistent-compilation-cache placement — ONE policy for every entry
point that compiles (``edl serve`` / ``generate`` / ``predict``, the
fleet replica, ``worker_main``, ``chip_smoke.py``, ``bench.py`` and the
``scripts/exp_*`` probes).

The directory is part of the cache key's lookup, so it must not move
between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set — the operator placed the cache
  (a volume that outlives the machine); JAX reads the variable itself
  and this module sets no directory in code.
* unset — one fixed directory inside the checkout, ``.jax_cache/``
  (git-ignored). Never a temp-dir, user, pid or time-derived name: a
  directory that moves never hits.

Call :func:`configure` right after ``import jax`` and before the first
compilation.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def configure() -> str:
    """Turn the persistent compile cache on; returns the directory in
    use (for logs — cold vs warm compile times only mean something next
    to the path they were taken against)."""
    env_dir = os.environ.get(_ENV)
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
