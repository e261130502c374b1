"""What a run records about itself: harness spans and the compile counter."""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Dict, Iterator, List


@dataclass
class Span:
    name: str
    t0: float
    t1: float
    index: int
    attrs: Dict = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Recorder:
    """Harness spans on the host clock, mirrored into the profiler's trace.

    Each span is also a ``jax.profiler.TraceAnnotation`` named
    ``<name>#<index>``, so a traced run can find it on the device's clock."""

    def __init__(self) -> None:
        self.spans: List[Span] = []

    @contextlib.contextmanager
    def span(self, name: str, **attrs) -> Iterator[Span]:
        import jax

        s = Span(name, 0.0, 0.0, len(self.spans), dict(attrs))
        with jax.profiler.TraceAnnotation(f"{name}#{s.index}"):
            s.t0 = time.perf_counter()
            try:
                yield s
            finally:
                s.t1 = time.perf_counter()
        self.spans.append(s)


class CompileCounter:
    """Counts XLA compiles that the persistent cache did not serve.

    JAX records one ``backend_compile_duration`` event for every executable it
    builds or loads, and one ``cache_hits`` event for each one that came from
    the persistent cache; in-memory cache hits record neither. Only events
    while :attr:`active` is true are counted."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self.active = False
        self.backend = 0
        self.hits = 0

    def _on_event(self, event: str, **_) -> None:
        if self.active and event == self.HIT:
            self.hits += 1

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if self.active and event == self.BACKEND:
            self.backend += 1

    def __enter__(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_listener(self._on_event)
        jax.monitoring.unregister_event_duration_listener(self._on_duration)

    @property
    def compiles(self) -> int:
        return self.backend - self.hits


def now() -> float:
    return time.perf_counter()

