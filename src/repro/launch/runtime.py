"""Process set-up shared by the entry points: the persistent compile cache
and the one-line statement of the devices a run uses.

Nothing here runs at import time. Each ``main()`` calls
``use_compile_cache()`` first, so the test workers (which import these
modules but never call ``main()``) write no cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/runtime.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and it
    is left alone. Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (gitignored): a fixed path, so that a second
    run on the same machine finds what the first compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_info() -> dict:
    """Platform, kind and count of the devices JAX gives this process."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_line() -> str:
    d = device_info()
    return (f"devices: platform={d['platform']} kind={d['kind']} "
            f"count={d['count']}")
