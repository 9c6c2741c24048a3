"""Executables obtained by the backend, counted from JAX's own monitoring
events.

``backend_compile_duration`` fires once for every executable the process
has to obtain, whether XLA compiles it or the persistent cache serves it;
``cache_hits`` fires for the latter.  Inside the measured window the
first count must stay 0: any event there is a program that was not
warmed up in set-up."""
from __future__ import annotations

import jax

_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self) -> None:
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, duration: float, **_) -> None:
        if event == _COMPILE:
            self.compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == _HIT:
            self.cache_hits += 1
