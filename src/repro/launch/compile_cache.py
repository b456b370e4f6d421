"""Persistent XLA compilation cache for the command-line entry points.

Called from the entry points only (``chip_smoke.py``,
``launch/schedule.py``, ``launch/scheduler_service.py``,
``benchmarks/run.py``, ``benchmarks/scheduler_ablation.py``), never on
import, so library users and the tests keep JAX's own defaults.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: fixed cache location inside the checkout (listed in .gitignore)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no other directory is set here.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``: a fixed path, never built from a temp
    name, a pid or the time, so the next run finds what this one wrote.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
