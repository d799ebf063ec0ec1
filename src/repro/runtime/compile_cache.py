"""JAX's persistent compilation cache, placed from outside.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.party_host``, ``repro.serve.frontend``) call
``enable_compile_cache()`` once at start-up, never at import time:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads that directory, and
  nothing else is set here;
- unset: the cache goes to ``<checkout>/.jax_cache``, a fixed path (the
  path is part of the cache key, so a directory that moves never hits).

``cache_stats()`` counts the persistent-cache hits and misses of the
process since the call.
"""
from __future__ import annotations

import os
import pathlib
from typing import Dict

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"
ENV = "JAX_COMPILATION_CACHE_DIR"

_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
_counts: Dict[str, int] = {"hits": 0, "misses": 0}
_listening = False


def cache_dir() -> str:
    """The directory the cache uses: ``$JAX_COMPILATION_CACHE_DIR`` if set,
    else ``<checkout>/.jax_cache``."""
    return os.environ.get(ENV) or str(DEFAULT_DIR)


def _on_event(event: str, **_kw) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        _counts[name] += 1


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    global _listening
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return cache_dir()


def cache_stats() -> Dict[str, int]:
    """Persistent-cache hits and misses counted since
    ``enable_compile_cache``."""
    return dict(_counts)
