"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`use_compile_cache` once at start-up; nothing calls it at import.
"""

from __future__ import annotations

import os
import pathlib

import jax


def use_compile_cache(root: str | os.PathLike) -> str:
    """Keep compiled programs across processes. ``JAX_COMPILATION_CACHE_DIR``
    wins when set (JAX reads it itself, and no directory is set here);
    otherwise the cache lives at the fixed ``<root>/.jax_cache``, so a
    later run from the same checkout finds what this one compiled.
    Every program is kept, not only those that took JAX's default of a
    second or more to compile: a serving engine compiles one short
    prefill program per prompt-length bucket. Returns the directory in
    use."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(pathlib.Path(root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
