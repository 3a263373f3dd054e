"""Every backend compile of this process, from jax's own monitoring events
(the count of ``chip_smoke.py::CompileMeter``, PR 21, copied so that it is
the benchmark's). A persistent-cache hit still passes
through the backend-compile event (its duration is then the retrieval), so
``compiles`` counts programs built or loaded and ``cache_hits`` how many were
loaded."""
from __future__ import annotations

from typing import Any

_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    def __init__(self) -> None:
        import jax.monitoring

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_: Any) -> None:
        if event == _COMPILE:
            self.compiles += 1

    def _event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
