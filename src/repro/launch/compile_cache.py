"""Where JAX keeps its persistent compilation cache.

The one place the cache directory is chosen. Entry points that run on the
chip (``chip_smoke.py``, ``benchmarks/common.py``) call
:func:`enable_compile_cache` before their first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: In-checkout fallback (listed in ``.gitignore``). A fixed path, never a
#: temp, pid or time-stamped one: a cache that moves is never hit again.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and nothing is set here. Otherwise the cache goes to :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
