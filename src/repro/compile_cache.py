"""Placement of JAX's persistent compilation cache for chip-facing
scripts (``chip_smoke.py``, ``benchmarks/bench_pallas.py``,
``benchmarks/bench_stream.py``); tests never call it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
other directory is set here. Otherwise the cache lives at the fixed
in-checkout directory ``CACHE_DIR`` (listed in ``.gitignore``): a path
that never carries a temporary name, pid or time, so a later run from
the same checkout finds what an earlier one compiled.
"""

from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns
    the directory in use."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # each wave_loop segment program compiles in well under JAX's
    # default 1 s admission threshold; keep them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir
