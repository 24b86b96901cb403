"""The one place that turns on JAX's persistent compilation cache.

Every entry point (``train/cli.py``, ``serve/__main__.py``,
``post/cli.py``, ``tests/conftest.py``) calls
``enable_compile_cache()`` before its first compile. The cache directory
is part of the cache key, so it has to be the same path in every process
that should share compiled programs:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  this helper sets no directory in code;
- unset: ``<checkout>/.jax_cache`` (listed in ``.gitignore``), a fixed
  path — never a temporary directory, a pid or a time.
"""
from __future__ import annotations

import dataclasses
import json
import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


@dataclasses.dataclass
class CacheUse:
    """Where this process caches compiled programs, and how many compile
    requests so far were served from there (``hits``) or compiled and
    written (``misses``), as JAX's own monitoring events count them."""
    directory: str
    hits: int = 0
    misses: int = 0

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def print_line(self) -> None:
        """One JSON line an entry point prints when it is done."""
        print(json.dumps({"compile_cache_use": dataclasses.asdict(self)}),
              flush=True)


def enable_compile_cache() -> CacheUse:
    """Turn the persistent cache on; returns the directory in use with
    live hit/miss counts."""
    import jax

    from_env = os.environ.get(CACHE_ENV)
    if not from_env:
        CHECKOUT_CACHE.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    # cache every program, however quick its compile: a cold chip call
    # pays for hundreds of small ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    use = CacheUse(from_env or str(CHECKOUT_CACHE))
    jax.monitoring.register_event_listener(use._on_event)
    return use
