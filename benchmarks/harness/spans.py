"""The benchmark's own host spans, and the count of compilations.

A span is recorded twice over: as (name, start, end) on the host's
clock, and, while the profiler runs, as a `jax.profiler.TraceAnnotation`
named `bench/<name>` in the profiler's own trace, where it shares a clock
with the device's operations.
"""

import contextlib
import time

import jax

SPAN_PREFIX = "bench/"


class Spans:
    def __init__(self):
        self.records = []  # (name, start, end) on time.perf_counter
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name):
        t0 = time.perf_counter()
        if self.tracing:
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
                yield
        else:
            yield
        self.records.append((name, t0, time.perf_counter()))

    def durations(self, name):
        """Seconds of each span called ``name``."""
        return [e - s for n, s, e in self.records if n == name]


class CompileCounter:
    """Counts the programs JAX compiles or fetches from its persistent
    cache (one `/jax/compilation_cache/compile_requests_use_cache` event
    each) from construction on: a warmed-up window must add none."""

    _EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **kwargs):
        if event == self._EVENT:
            self.count += 1
