"""Count what JAX compiles inside a block (copied from ``chip_smoke.py``'s
``watch_compiles``, without its StableHLO dump: the benchmark only counts).

``programs`` counts the programs JAX had to make ready (the backend-compile
event fires for each, whether XLA compiled it or it was read from the
persistent cache), ``cache_hits``/``cache_misses`` the persistent-cache
lookups, and ``seconds`` the time those took. Inside the window every count
must be 0.
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax


@dataclasses.dataclass
class CompileLog:
    programs: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    seconds: float = 0.0


@contextlib.contextmanager
def watch_compiles():
    log = CompileLog()

    def on_duration(event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            log.programs += 1
            log.seconds += secs

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            log.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            log.cache_misses += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield log
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)
