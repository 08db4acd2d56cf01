"""Where the entry points keep JAX's persistent compile cache, and how many
compilations a run paid for.

``chip_smoke.py``, ``repro.launch.serve`` and ``benchmarks.run`` call
``enable_compile_cache()`` once, before their first compilation, so every
process of a run shares one cache: a cold run compiles, a warm one reads
back what the cold run wrote.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, git-ignored, inside the checkout: the cache is looked up under its
# path, so a directory that moved between runs (a temp name, a pid, the
# time) would never be hit again
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, names it: JAX reads that
    variable itself, and no other directory is set here. Otherwise the
    cache lives in ``CACHE_DIR``. Every program is written, not only those
    over JAX's default one second of compile: a serving run compiles tens
    of sub-second programs, and a rerun should read them all back."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


class CompileCounter:
    """Counts backend compilations and their seconds from JAX's monitoring
    events, from construction on. A compilation served from the persistent
    cache still counts (its seconds are the read-back) and is also counted
    in ``cache_hits``, so a warm run shows as hits and few seconds."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def summary(self) -> str:
        return (f"compilations={self.count} compile_s={self.seconds:.1f} "
                f"persistent_cache_hits={self.cache_hits}")
