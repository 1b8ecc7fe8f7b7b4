"""Compile counting and host-clock timing of calls into the trainer."""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def compile_log():
    """Seconds of each XLA compile and the number of persistent-cache hits
    while the context is open, from JAX's monitoring events."""
    import jax
    log = {"compile_s": [], "cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            log["compile_s"].append(secs)

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            log["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield log
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)
