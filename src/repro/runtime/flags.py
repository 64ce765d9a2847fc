"""Runtime flags and process-wide settings the entry points apply.

``REPRO_BASELINE=1`` disables the beyond-paper collective-layout
optimizations (EXPERIMENTS.md §Perf iterations 2/4/5), reverting to the
paper-faithful baseline system — so both rows of the before/after tables are
reproducible from the same tree:

  * MoE dispatch-scatter local-domain pinning (iter. 2),
  * never-gather cross-entropy + replicated small dims of embed/lm_head
    (iter. 4),
  * flash-decoding (slot-parallel) decode layout (iter. 5).
"""

from __future__ import annotations

import os
from pathlib import Path

#: The persistent compilation cache's directory when the environment names
#: none: one fixed path in the checkout, so every run reuses it.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def baseline_mode() -> bool:
    return os.environ.get("REPRO_BASELINE", "") not in ("", "0")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone; otherwise the cache goes to :data:`COMPILE_CACHE_DIR`.
    Entry points call this (``chip_smoke.py``, ``repro.launch.serve``,
    ``benchmarks.run``); importing a module never does.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
