"""Compile and persistent-cache counters, from ``jax.monitoring``.

Kept with the benchmark, and read by ``chip_smoke.py`` too, so that the
benchmark's reading of jax's trace, lowering and compile durations cannot
move with the program.  ``backend_compile_duration`` wraps both a real
compile and a load from the persistent cache; ``misses`` counts the real
compiles.
"""

from __future__ import annotations

import dataclasses

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"


@dataclasses.dataclass
class Snapshot:
    trace_s: float = 0.0
    lower_s: float = 0.0
    compile_s: float = 0.0   # compile or load from the persistent cache
    hits: int = 0
    misses: int = 0

    def __sub__(self, other: "Snapshot") -> "Snapshot":
        return Snapshot(*(getattr(self, f.name) - getattr(other, f.name)
                          for f in dataclasses.fields(self)))

    @property
    def total_s(self) -> float:
        return self.trace_s + self.lower_s + self.compile_s


class Counters:
    """Running totals of the events above, for the whole process."""

    def __init__(self, jax):
        self.now = Snapshot()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == TRACE:
            self.now.trace_s += duration
        elif event == LOWER:
            self.now.lower_s += duration
        elif event == COMPILE:
            self.now.compile_s += duration

    def _event(self, event, **_):
        if event == HIT:
            self.now.hits += 1
        elif event == MISS:
            self.now.misses += 1

    def snapshot(self) -> Snapshot:
        return dataclasses.replace(self.now)
