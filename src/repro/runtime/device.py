"""The device a run uses, and where its compiled programs are cached.

Entry points call :func:`use_compile_cache` before their first compile.
JAX's persistent compilation cache is keyed by the cache directory among
other things, so the directory must not move between runs: it is
``$JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise the fixed
``<repo>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from typing import Dict

import jax

__all__ = ["REPO_CACHE_DIR", "describe_devices", "use_compile_cache"]

#: The cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset.
REPO_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache")
)


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory.

    Returns the directory.  Sets no other cache option.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or REPO_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def describe_devices() -> Dict[str, object]:
    """``{platform, kind, count}`` of the devices JAX found, as JAX reports
    them — the tag every device measurement carries."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
