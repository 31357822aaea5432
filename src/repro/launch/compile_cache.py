"""JAX's persistent compilation cache: one rule, one place.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``repro.launch.train``)
call :func:`enable_compile_cache` before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives there and nowhere else;
otherwise it lives at a fixed path inside the checkout.  The directory is
part of what a cached entry is found by, so it is never a temporary, per-pid
or per-run name.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (listed in .gitignore)
DEFAULT_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
