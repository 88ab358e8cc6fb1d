"""Persistent XLA compilation cache for the device runner.

The supervisor's crash/degrade/restart discipline made the runner
crash-only, and every start of the program compiles every kernel shape
before serving at full speed. jax's persistent compilation cache keeps
compiled executables on disk, so a respawned runner — and the next run
of the program — loads them instead.

The directory is part of the cache key's lookup, so it must not move:

  1. `JAX_COMPILATION_CACHE_DIR` set: jax reads it natively and this
     module sets no other (the environment places the cache);
  2. otherwise one fixed path in the checkout, `<repo>/.jax_cache`,
     derived from this package's location — never from a datastore
     directory, the home directory, a temporary name, a pid or a clock.

Only the dedicated runner subprocess calls `initialize()`; inline mode
shares the serving process's jax and gets a cache only through (1).
This module never imports jax at module level.
"""

from __future__ import annotations

import os
from typing import Optional

_INITIALIZED: Optional[dict] = None

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))


def cache_dir() -> str:
    """The one directory compiled kernels persist in."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(_REPO_ROOT, ".jax_cache")


def entry_count(path: str) -> int:
    """Cache entries on disk (jax writes `<key>-cache` payloads next
    to `<key>-atime` access stamps; only the payloads are entries)."""
    try:
        return sum(1 for e in os.scandir(path)
                   if not e.name.endswith("-atime"))
    except OSError:
        return 0


def initialize() -> dict:
    """Point jax's persistent compilation cache at `cache_dir()` and
    cache every kernel regardless of size or compile time. Idempotent;
    returns {"dir", "entries", "from_env"}."""
    global _INITIALIZED
    if _INITIALIZED is not None:
        return _INITIALIZED
    import jax

    d = cache_dir()
    from_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    os.makedirs(d, exist_ok=True)
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", d)
    # small serving kernels compile in well under the default 1 s
    # floor — cache everything, the bucket ladder bounds the count
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _INITIALIZED = {"dir": d, "entries": entry_count(d),
                    "from_env": from_env}
    return _INITIALIZED


def reset_for_tests():
    """Drop the idempotence latch."""
    global _INITIALIZED
    _INITIALIZED = None
