"""Backend compiles and persistent-cache hits, from jax's own monitoring
events. A cache hit's retrieval is reported as a compile too, so a warm
run counts its cache loads here; `seconds` then is load time."""
from __future__ import annotations

import threading


class CompileMeter:
    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.seconds = 0.0
        self.n_compiles = 0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                with self._lock:
                    self.seconds += duration
                    self.n_compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                with self._lock:
                    self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple[int, float, int]:
        with self._lock:
            return self.n_compiles, self.seconds, self.cache_hits
