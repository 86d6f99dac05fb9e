"""Backend compiles and persistent-cache hits, seen through
``jax.monitoring``.  (Adapted from ``CompileLog`` in the repo root's
``chip_smoke.py``; here each compile keeps the time it ended, so that a
window can count the compiles inside it.)"""

from __future__ import annotations

import threading
import time

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Listeners fire on whichever thread compiles (the engine's workers
    among them), so every update holds the lock."""

    def __init__(self, clock=time.monotonic):
        self._clock = clock
        self._lock = threading.Lock()
        self.compiles = []          # (end time, function name, seconds)
        self.hits = 0

    def on_duration(self, event, duration, **kw):
        if event == BACKEND_COMPILE_EVENT:
            with self._lock:
                self.compiles.append((self._clock(), kw.get("fun_name"),
                                      duration))

    def on_event(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.hits += 1

    def register(self) -> "CompileLog":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def between(self, t0: float, t1: float) -> int:
        """Backend compiles that ended in ``[t0, t1]``."""
        with self._lock:
            return sum(1 for t, _, _ in self.compiles if t0 <= t <= t1)

    def summary(self) -> str:
        with self._lock:
            n, s = len(self.compiles), sum(c[2] for c in self.compiles)
            return (f"{n} backend compiles in {s:.3f} s, {self.hits} "
                    f"persistent-cache hits")
