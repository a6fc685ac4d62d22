"""JAX's persistent compilation cache, kept at one fixed path.

A cache in a directory that changes from run to run (a temporary, a pid
or a time in its name) is never hit again, so the directory is fixed: the one
``JAX_COMPILATION_CACHE_DIR`` names when it is set, otherwise
``<repo>/.jax_cache``.  Entry points call :func:`enable_compile_cache`
before their first compile; importing this module changes nothing.

A program's cache key must not depend on where the checkout lives
either.  JAX strips source locations from a program before hashing it,
but a Pallas kernel travels as a serialized Mosaic module inside its
custom call, locations included; so the checkout's own path is cut from
every source location, and the same tree hits the same entries from any
directory.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(REPO_ROOT) + os.sep))
    return path
