"""Persistent XLA compilation cache for the entry points that compile.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the
cache lives there; nothing here names another directory. Otherwise the
cache goes to the fixed path ``<repo>/.jax_cache`` (gitignored): the
directory is part of each entry's key, so a name that changed from run to
run (a temporary directory, a pid, the time) would never hit.

Importing ``repro`` sets nothing. ``chip_smoke.py`` and the serve and
train launchers call :func:`enable` before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
