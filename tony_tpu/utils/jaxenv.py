"""Where this process's JAX keeps compiled programs, and which device it
found. Both are process-wide facts every entry point states the same way.

Compile cache: JAX's persistent compilation cache keys on the directory
path, so a directory that moves (a temp name, a pid, a timestamp) never
hits. ``JAX_COMPILATION_CACHE_DIR`` places it from outside — JAX reads
that variable itself, and nothing here overrides it. Unset, the cache
lives at one fixed, git-ignored directory inside the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_compile_cache (listed in .gitignore)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_compile_cache"


def place_compile_cache() -> str:
    """Call first thing in an entry point, before anything compiles.
    Returns the directory in use."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def device_report() -> dict:
    """{"platform", "kind", "count"} as JAX reports them — carried by
    every result an entry point prints, so a number names where it ran —
    plus the bytes each local device holds right now (None where the
    runtime serves no allocator stats): a model meant to be sharded that
    sits whole on the first device shows here."""
    import jax

    devices = jax.devices()
    held = [(d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.local_devices()]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "bytes_in_use": held}
