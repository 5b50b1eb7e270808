"""Process-level runtime choices, each decided in one place.

* :func:`pallas_interpret` — whether a Pallas kernel runs in interpret
  mode.  An explicit flag wins (tests steer it; compile rehearsals for a
  described TPU pass ``False`` from a CPU process); otherwise it follows
  the backend this process actually has: compiled on a TPU, interpreted
  everywhere else (``JAX_PLATFORMS=cpu``).
* :func:`enable_compile_cache` — the persistent compilation cache that
  entry points (launchers, benchmarks, ``chip_smoke.py``) switch on.

Nothing here touches JAX device state at import.
"""
from __future__ import annotations

import os
import pathlib
from typing import Optional

import jax

#: Fixed in-checkout cache directory used when the environment names none;
#: a fixed path is part of the cache key, so runs from one checkout hit.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def pallas_interpret(flag: Optional[bool] = None) -> bool:
    """Resolve a Pallas ``interpret`` flag: ``None`` means "decide from
    the platform" — never interpreted on a TPU backend, always on any
    other (the CPU container has no Mosaic backend to compile for)."""
    if flag is not None:
        return bool(flag)
    return jax.default_backend() != "tpu"


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else :data:`CACHE_DIR`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)


def enable_compile_cache() -> str:
    """Switch on JAX's persistent compilation cache and return its path.

    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and
    no other directory is configured here; otherwise the cache goes to
    the fixed :data:`CACHE_DIR` inside the checkout."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
