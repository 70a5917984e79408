"""JAX's persistent compilation cache: one place that decides where it lives.

Every entry point (``repro.run``, ``repro.serve``, ``repro.scenarios``,
``repro.population``, the ``benchmarks/`` scripts, ``chip_smoke.py``) calls
``enable_compilation_cache()`` before it compiles anything.

* Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it at import and
  this module sets nothing.
* Otherwise the cache lives at ``<checkout>/.jax_cache`` (git-ignored): a
  fixed path, so a later process in the same checkout finds what an earlier
  one compiled, including the pool workers of a parallel sweep.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
