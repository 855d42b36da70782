"""JAX's persistent compilation cache for the entry points.

A cold process compiles every bucket program again, and on a TPU that is a
large part of a short run.  The entry points (``chip_smoke.py``,
``benchmarks/run.py``, ``serve_campaigns`` and the examples) call
``enable_compile_cache()`` before their first compile.  The tests never do.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache lives in ``.jax_cache/`` at the
root of the checkout: a fixed path, because the cache key includes it, so
a per-process or temporary directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CACHE_NAME = ".jax_cache"


def checkout_root(module_file: str = __file__) -> Path:
    """The checkout ``repro`` is imported from: three levels above
    ``src/repro/launch/``, which must hold ``pyproject.toml``.  An installed
    copy has no checkout, and a cache beside site-packages would be shared
    by every checkout, so that case raises: set the variable there."""
    root = Path(module_file).resolve().parents[3]
    if not (root / "pyproject.toml").is_file():
        raise RuntimeError(
            f"repro is not imported from a checkout ({root} holds no "
            f"pyproject.toml): set {ENV_VAR} to choose the compile cache")
    return root


def cache_dir_to_set(environ: Mapping[str, str] = os.environ,
                     module_file: str = __file__) -> Optional[str]:
    """The directory this module would configure: None where the
    environment already names one (JAX then uses that)."""
    if environ.get(ENV_VAR):
        return None
    return str(checkout_root(module_file) / CACHE_NAME)


def enable_compile_cache() -> str:
    """Switch the persistent cache on; returns the directory in use."""
    import jax

    path = cache_dir_to_set()
    if path is None:
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", path)
    return path
