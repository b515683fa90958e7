"""Counts of XLA compilations, from jax's own monitoring events."""

from __future__ import annotations

from typing import Dict


class CompileWatch:
    """Backend compile seconds and compile requests, split at `mark()`:
    what came before it is set-up, what comes after is inside the window."""

    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.totals: Dict[str, float] = {"requests": 0, "hits": 0,
                                         "misses": 0, "backend_s": 0.0,
                                         "backend_compiles": 0}
        self._marks: Dict[str, Dict[str, float]] = {}

    def install(self) -> None:
        import jax
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)

    def _on_event(self, event, **_):
        if event == self.REQUEST:
            self.totals["requests"] += 1
        elif event == self.HIT:
            self.totals["hits"] += 1
        elif event == self.MISS:
            self.totals["misses"] += 1

    def _on_secs(self, event, duration_secs, **_):
        if event == self.BACKEND:
            self.totals["backend_s"] += float(duration_secs)
            self.totals["backend_compiles"] += 1

    def mark(self, name: str) -> None:
        self._marks[name] = dict(self.totals)

    def between(self, start: str, end: str) -> Dict[str, float]:
        a, b = self._marks[start], self._marks[end]
        return {k: b[k] - a[k] for k in b}

    def until(self, end: str) -> Dict[str, float]:
        return dict(self._marks[end])
