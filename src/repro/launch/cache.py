"""JAX persistent compilation cache placement.

Entry points that run on the chip (`chip_smoke.py`, `benchmarks/run.py`)
call `enable_compile_cache` once, before their first compile. Tests do
not: they compile for the CPU and for described (unattached) TPUs, whose
entries could not be read back.
"""
from __future__ import annotations

import os


def enable_compile_cache(root: str) -> str:
    """Turn JAX's persistent compilation cache on and return its
    directory. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it
    itself and nothing else is set here; otherwise the cache goes to
    the fixed directory `<root>/.jax_cache` — a fixed path, because the
    path is part of what makes a later run find the entries again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
