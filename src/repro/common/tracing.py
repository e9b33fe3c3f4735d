"""Host spans and counters of the FL round.

Tracing is off by default. Then `span` hands back one shared no-op
context manager after a single check, and `count` returns at once.

    rec = tracing.start()                # before the run
    ...                                  # FLCloudRunner(...).run()
    tracing.stop()
    rec.spans      # Span(name, start_ns, end_ns, parent, round), in the
                   # order they opened; parent is an index into spans
    rec.counters   # {"rounds": 3, "compiles": 4, "compile_s": ..., ...}

Span times are `time.perf_counter_ns`. Each span's parent is the span
open around it, and a span with no `round` of its own takes its
parent's, so every span of one FL round carries that round's index.
While tracing is on, each span is also a `jax.profiler.TraceAnnotation`
of the same name, so a profiler trace taken meanwhile shows the spans on
the host plane, on the device trace's clock (with no profiler running
the annotation records nothing). JAX's monitoring events fill the
counters `compiles` and `compile_s` (programs compiled or loaded from
the persistent compilation cache, and their seconds) and `cache_hits`
(the loads among them). The tracer keeps one recorder for the process
and is meant for the thread that drives the FL run.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

_NULL = contextlib.nullcontext()
_recorder: Optional["Recorder"] = None

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass(frozen=True)
class Span:
    """One recorded interval: `parent` is the index of the span open
    around it in `Recorder.spans`, `round` the FL round it belongs to."""
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[int]
    round: Optional[int]


class Recorder:
    """The spans and counters of one tracing session."""

    def __init__(self):
        self.spans: List[Span] = []
        self.counters: Dict[str, float] = {}
        self._open: List[int] = []

    @contextlib.contextmanager
    def span(self, name: str, round: Optional[int] = None):
        """Record `name` around the `with` block, and annotate it for
        the profiler."""
        import jax
        parent = self._open[-1] if self._open else None
        if round is None and parent is not None:
            round = self.spans[parent].round
        i = len(self.spans)
        self.spans.append(Span(name, time.perf_counter_ns(), -1, parent,
                               round))
        self._open.append(i)
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self._open.pop()
            self.spans[i] = dataclasses.replace(
                self.spans[i], end_ns=time.perf_counter_ns())

    def count(self, name: str, n: float = 1) -> None:
        """Add `n` to the counter `name`."""
        self.counters[name] = self.counters.get(name, 0) + n

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count("compiles")
            self.count("compile_s", secs)

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.count("cache_hits")

    def named(self, name: str) -> List[Span]:
        """The spans called `name`, in the order they opened."""
        return [s for s in self.spans if s.name == name]

    def self_ns(self, i: int) -> int:
        """Span `i`'s time outside its direct children."""
        s = self.spans[i]
        return (s.end_ns - s.start_ns) - sum(
            c.end_ns - c.start_ns for c in self.spans if c.parent == i)


def span(name: str, round: Optional[int] = None):
    """A context manager that records `name` while tracing is on."""
    if _recorder is None:
        return _NULL
    return _recorder.span(name, round)


def count(name: str, n: float = 1) -> None:
    """Add `n` to the counter `name` while tracing is on."""
    if _recorder is not None:
        _recorder.count(name, n)


def start() -> Recorder:
    """Turn tracing on and return the session's recorder."""
    global _recorder
    import jax
    if _recorder is not None:
        raise RuntimeError("tracing is already on")
    rec = Recorder()
    jax.monitoring.register_event_duration_secs_listener(rec._on_duration)
    jax.monitoring.register_event_listener(rec._on_event)
    _recorder = rec
    return rec


def stop() -> Recorder:
    """Turn tracing off and return the session's recorder."""
    global _recorder
    import jax
    rec, _recorder = _recorder, None
    if rec is None:
        raise RuntimeError("tracing is off")
    jax.monitoring.unregister_event_duration_listener(rec._on_duration)
    jax.monitoring.unregister_event_listener(rec._on_event)
    return rec

