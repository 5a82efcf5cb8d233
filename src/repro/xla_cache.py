"""Persistent XLA compilation cache.

The serving stack compiles one executable per (arch, batch bucket, chunk
bucket, packed-token bucket) combination; a cold process pays that XLA
compile time again even though nothing changed. Pointing jax at an
on-disk compilation cache makes warm starts (repeat benchmark runs, CI
jobs restoring the cache directory, kernel restarts on one machine) skip
straight to execution.

Enabled automatically on ``import repro`` unless ``REPRO_XLA_CACHE=0``.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
module sets no directory; otherwise the cache lives at ``.jax_cache`` in
the checkout, an absolute path derived from this file's own location, so
every process of the checkout shares one cache whatever its working
directory (the path is part of what makes an entry hit). Every knob is
exception-guarded: a read-only filesystem or a broken cache dir must
degrade to plain compilation, never break an import.
"""
from __future__ import annotations

import os
from typing import Optional

# <checkout>/.jax_cache: this file is <checkout>/src/repro/xla_cache.py
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")

_enabled_dir: Optional[str] = None


def enable_persistent_cache() -> Optional[str]:
    """Turn jax's persistent compilation cache on and return the directory
    it uses (None when disabled or unavailable)."""
    global _enabled_dir
    if os.environ.get("REPRO_XLA_CACHE", "1") == "0":
        return None
    try:
        import jax
        path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not path:
            path = DEFAULT_DIR
            jax.config.update("jax_compilation_cache_dir", path)
        # cache every executable: the serving buckets are individually
        # small but collectively the whole warm-start win
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        # a FINITE max_size is load-bearing, not just hygiene: jax 0.9's
        # LRUCache (jax/_src/lru_cache.py) only takes its cross-process
        # filelock when eviction is enabled, and its writes are plain
        # write_bytes (no tmp+rename) -- unbounded mode lets a concurrent
        # reader see a half-written executable and crash in native
        # deserialization
        jax.config.update("jax_compilation_cache_max_size", 1 << 30)
    except Exception:           # noqa: BLE001 -- degrade, never break import
        return None
    _enabled_dir = path
    return path


def cache_dir() -> Optional[str]:
    """The configured cache directory, or None when the cache is off."""
    return _enabled_dir
