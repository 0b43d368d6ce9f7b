"""Compile accounting for a benchmark run.

``CompileClock`` listens to JAX's own compile events: the union of its
tracing, lowering and backend-compile (or cache-retrieval) spans — a union,
because tracing one jit nests the tracing of those it calls — plus the
persistent-cache hits and the number of backend compiles.  The harness
reads it to split ``setup_s`` into compiling and the rest, and to count
the compiles inside the measured window (there should be none).
"""

from __future__ import annotations

import threading
import time

EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Compile spans and counts of the thread that built it."""

    def __init__(self):
        import jax

        self.spans: list[tuple[float, float]] = []
        self.backend_compiles = 0
        self.cache_hits = 0
        self._thread = threading.get_ident()
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    # listeners run synchronously on the thread that compiles
    def _span(self, event, start, end, **_):
        if event in EVENTS and threading.get_ident() == self._thread:
            self.spans.append((start, end))
            if event == BACKEND_COMPILE:
                self.backend_compiles += 1

    def _event(self, event, **_):
        if event == CACHE_HIT and threading.get_ident() == self._thread:
            self.cache_hits += 1

    def traces(self) -> int:
        """Number of compile spans seen so far (any kind)."""
        return len(self.spans)

    def seconds_since(self, t0: float) -> float:
        """Seconds of compiling (the union of the spans) since ``t0``, on
        ``time.time()``'s clock."""
        total, reach = 0.0, t0
        for start, end in sorted(sp for sp in self.spans if sp[0] >= t0):
            start = max(start, reach)
            if end > start:
                total += end - start
                reach = end
        return total


def now() -> float:
    """The compile spans' clock."""
    return time.time()
