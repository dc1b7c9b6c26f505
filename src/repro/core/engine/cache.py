"""Persistent XLA compilation cache placement (compile amortization).

Every engine configuration compiles its cycle loop once per process; for
grid sweeps driven from short-lived processes (benchmarks, the chip
smoke run) that first compile dominates wall time.  JAX's persistent
compilation cache lets a later process start from the serialized
executable instead of recompiling.  The cache is keyed partly on its
directory, so the directory must not move between processes:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself at import;
    :func:`enable_persistent_cache` leaves JAX's settings alone;
  * unset — the cache goes to the fixed ``<repo>/.jax_cache``.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``benchmarks/perf.py``) call :func:`enable_persistent_cache` once at
start-up; library code (``SimEngine``) never does.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = str(Path(__file__).resolve().parents[4] / ".jax_cache")


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compile cache; return its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
