"""Host clocks: compile accounting and the benchmark's own spans.

`CompileClock` is copied from the program's chip_smoke.py: it sums
JAX's backend-compile durations and counts compiles and persistent-cache
hits from JAX's own monitoring events.

`Spans` records host intervals around the benchmark's calls into the
program (perf_counter_ns), and writes each as a profiler TraceAnnotation
as well, so that a traced run can label device idle gaps by what the
host was doing.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Tuple


class CompileClock:
    """Inside its `with` block, sums JAX's backend-compile durations
    (trace events nest, so they are left out), and counts backend
    compiles and persistent-cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


class Spans:
    """Named host intervals in nanoseconds of `time.perf_counter_ns`."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.spans: Dict[str, List[Tuple[int, int]]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        with ann:
            t0 = time.perf_counter_ns()
            try:
                yield
            finally:
                self.spans.setdefault(name, []).append(
                    (t0, time.perf_counter_ns()))
