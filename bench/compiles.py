"""Backend compiles, from JAX's own monitoring events.

JAX records ``backend_compile_duration`` for every executable it builds,
also when it loads one from the persistent compilation cache; a load also
records a ``cache_hits`` event. So the compiles that really ran are the
first count less the second.
"""
from __future__ import annotations

COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    def __init__(self):
        import jax

        self.events = 0
        self.seconds = 0.0
        self.hits = 0

        def on_duration(event, secs, **_):
            if event == COMPILE:
                self.events += 1
                self.seconds += secs

        def on_event(event, **_):
            if event == HIT:
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @property
    def compiled(self) -> int:
        return self.events - self.hits
