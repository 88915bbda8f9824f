"""Seconds JAX spends making programs, from its own monitoring events.

Tracing, lowering, backend compilation and reads of the persistent
compilation cache each report a duration event; every one of them is
set-up work, and any of them inside the measured window means a program
was built there.
"""
from __future__ import annotations

import threading

import jax

EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


class CompileClock:
    """Sums the ``EVENTS`` durations by name since the last ``take``."""

    def __init__(self):
        self._lock = threading.Lock()
        self._seen: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in EVENTS:
            with self._lock:
                n, s = self._seen.get(event, (0, 0.0))
                self._seen[event] = (n + 1, s + duration)

    def take(self) -> dict:
        """``{event: (count, seconds)}`` since the last call."""
        with self._lock:
            seen, self._seen = self._seen, {}
        return seen
