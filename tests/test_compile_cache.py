"""Placement of the persistent compilation cache (`repro.compile_cache`).

Each case runs a fresh interpreter: JAX initializes its cache once per
process, so the placement can only be observed from a clean start.
"""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]

#: enable the cache (optionally pointing its default somewhere else), then
#: compile one tiny program; prints the directory enable() reported
PROGRAM = """
import sys
from pathlib import Path
from repro import compile_cache
if len(sys.argv) > 1:
    compile_cache.DEFAULT_DIR = Path(sys.argv[1])
print(compile_cache.enable())
import jax, jax.numpy as jnp
jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)))
"""


def _run(env_dir, *argv) -> str:
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT / "src"), env.get("PYTHONPATH", "")])
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run(
        [sys.executable, "-c", PROGRAM, *map(str, argv)],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.strip().splitlines()[-1]


def test_default_dir_is_fixed_under_the_repo():
    assert compile_cache.DEFAULT_DIR == ROOT / ".jax_cache"


@pytest.mark.parametrize("where", ["env", "default"])
def test_entries_land_only_in_the_chosen_dir(tmp_path, where):
    chosen, other = tmp_path / "chosen", tmp_path / "other"
    if where == "env":
        # the variable wins; the default (pointed at `other`) stays unused
        reported = _run(chosen, other)
    else:
        reported = _run(None, chosen)
    assert reported == str(chosen)
    entries = list(chosen.iterdir())
    assert any(p.name.startswith("jit__lambda") for p in entries), entries
    assert not other.exists()
