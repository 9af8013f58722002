"""JAX's persistent compilation cache, in one fixed place.

A cold process on the chip recompiles every pow2 lookup bucket, the kernel
and the collectives.  `enable_persistent_cache()` keeps those executables on
disk so the next process on the same machine loads them instead:

  * when `JAX_COMPILATION_CACHE_DIR` is set, jax reads it itself and this
    module sets no directory;
  * otherwise the cache is `<repo>/.jax_cache`, a fixed path (the path is
    part of what makes an entry findable again).

Entry points call it (`chip_smoke.py`, `benchmarks/run.py`); library code
and tests never do.  `cache_counts()` reports the hits and misses the
process has seen since.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
_counts = {"hits": 0, "misses": 0}
_lock = threading.Lock()
_listening = False


def _on_event(event: str, **kw) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        with _lock:
            _counts[name] += 1


def enable_persistent_cache() -> str:
    """Turn the persistent cache on for this process and return its
    directory.  Every executable is cached, however quick its compile."""
    global _listening
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
    return cache_dir


def cache_counts() -> dict:
    """{"hits", "misses"} of the persistent cache in this process."""
    with _lock:
        return dict(_counts)
