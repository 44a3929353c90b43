"""Persistent XLA compilation cache + compile-time accounting.

One rule for every entry point that compiles (``tmpi`` train / serve /
profile, ``benchmark/``): where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and this module sets nothing; otherwise the cache lives
at ONE fixed, git-ignored path inside the checkout. The directory is
part of every cache key, so a temp name, a pid or a timestamp in it
would never hit.
"""

from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache (the package is run from its checkout, not
# pip-installed — see .claude/skills/verify/SKILL.md)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def enable_compile_cache() -> str:
    """Point JAX at the persistent cache BEFORE the first compile and
    return the directory in use. The environment variable wins: with it
    set, nothing is configured in code."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


class CompileClock:
    """Sums this process's XLA backend-compile seconds (a persistent-
    cache retrieval counts as the short "compile" it is) and counts
    cache hits, from JAX's own monitoring events. Create ONE per
    process, before the first compile; JAX offers no public way to
    unregister a listener."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == _BACKEND_COMPILE:
            self.seconds += float(duration)
            self.programs += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def report(self) -> dict:
        return {
            "compile_seconds": round(self.seconds, 3),
            "compiled_programs": self.programs,
            "compile_cache_hits": self.cache_hits,
        }
