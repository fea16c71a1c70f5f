"""Placement of JAX's persistent compilation cache for the entry points.

`enable()` is called once, before the first compile, by `chip_smoke.py`,
`benchmarks/run.py` and `scripts/run.py`:

* if `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing
  here overrides it;
* otherwise the cache lives at the fixed `<repo>/.jax_cache` (gitignored).
  The path is part of every entry's key, so it never depends on a
  temporary name, a pid or the time.

Every compile is cached, however short: the served path compiles many
small per-signature programs (one per codec signature x gang size), each
well under JAX's default one-second threshold.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: default cache directory: `<repo>/.jax_cache`
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
