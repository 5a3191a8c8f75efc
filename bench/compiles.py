"""Count XLA backend compiles of this process on the host clock."""

from __future__ import annotations

import time
from typing import List, Tuple

import jax


class CompileLog:
    """Host-clock end time of every XLA backend compile in this process.

    JAX reports a backend compile through ``jax.monitoring`` whether or not
    the persistent cache then serves it: :attr:`cache_hits` counts the
    programs loaded from the cache, :attr:`cache_misses` those compiled."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"
    CACHE_MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self) -> None:
        self.times: List[float] = []
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == self.COMPILE:
            self.times.append(time.perf_counter())

    def _on_event(self, event: str, **_) -> None:
        if event == self.CACHE_HIT:
            self.cache_hits += 1
        elif event == self.CACHE_MISS:
            self.cache_misses += 1

    def within(self, window: Tuple[float, float]) -> int:
        t0, t1 = window
        return sum(1 for t in self.times if t0 <= t <= t1)
