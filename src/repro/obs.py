"""Host spans and JAX compile counters: the program's only tracing.

`span(name)` and `step_span(step)` name a stretch of host time. They always
enter a `jax.profiler.TraceAnnotation` (a `StepTraceAnnotation` for a step),
so a running profiler puts them on its host plane, on the clock of the
device ops: XProf's trace viewer shows them, and its step view reads the
steps. While a `Recorder` is installed they are also kept in memory, with
the compiles and compilation-cache events JAX reports, for a caller that
reads them (a benchmark, a test). With none installed a span costs the
annotation object and one `is None` test.

    rec = Recorder().install()
    try:
        train_loop(...)
    finally:
        rec.uninstall()
    rec.spans, rec.counters
"""
from __future__ import annotations

import contextlib
import time

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_misses"}
_CACHE_SECONDS = "/jax/compilation_cache/cache_retrieval_time_sec"

_installed: Recorder | None = None


class Recorder:
    """Spans and counters, kept in memory while installed.

    `spans`: (name, parent, start, end) in the order the spans started, in
    `time.perf_counter()` seconds; `parent` is the index in `spans` of the
    enclosing span, or None, and `end` is None while a span is open. A
    backend compile (or a load from the persistent cache) is the span
    `compile:<function name>` under the span that was open while it ran.
    `counters`: the persistent compilation cache's hits and misses, and the
    seconds spent reading its hits."""

    def __init__(self):
        self.spans: list = []
        self.counters = {"cache_hits": 0, "cache_misses": 0,
                         "cache_retrieval_s": 0.0}
        self._open: list = []  # indices of the spans entered and not left

    def install(self) -> Recorder:
        global _installed
        if _installed is not None:
            raise RuntimeError("a Recorder is already installed")
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_time_span_listener(self._on_time_span)
        _installed = self
        return self

    def uninstall(self):
        global _installed
        if _installed is not self:
            raise RuntimeError("this Recorder is not the one installed")
        jax.monitoring.unregister_event_listener(self._on_event)
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_time_span_listener(self._on_time_span)
        _installed = None

    def _enter(self, name: str) -> int:
        i = len(self.spans)
        parent = self._open[-1] if self._open else None
        self.spans.append((name, parent, time.perf_counter(), None))
        self._open.append(i)
        return i

    def _leave(self, i: int):
        name, parent, start, _ = self.spans[i]
        self.spans[i] = (name, parent, start, time.perf_counter())
        self._open.remove(i)

    def _on_event(self, event: str, **_):
        if event in _CACHE_COUNTS:
            self.counters[_CACHE_COUNTS[event]] += 1

    def _on_duration(self, event: str, duration: float, **_):
        if event == _CACHE_SECONDS:
            self.counters["cache_retrieval_s"] += duration

    def _on_time_span(self, event: str, start: float, end: float, **kw):
        if event != COMPILE_EVENT:
            return
        # JAX stamps a compile with time.time(), at its end: move it onto
        # the spans' clock
        shift = time.perf_counter() - time.time()
        parent = self._open[-1] if self._open else None
        self.spans.append((f"compile:{kw.get('fun_name', '?')}", parent,
                           start + shift, end + shift))


@contextlib.contextmanager
def _recorded(annotation, name: str):
    with annotation:
        rec = _installed
        if rec is None:
            yield None
            return
        i = rec._enter(name)
        try:
            yield rec
        finally:
            rec._leave(i)


def span(name: str):
    """Context manager for a named stretch of host time; yields the
    installed Recorder, or None."""
    return _recorded(jax.profiler.TraceAnnotation(name), name)


def step_span(step: int):
    """`span("train.step")` that the profiler's step view reads as step
    `step`."""
    return _recorded(jax.profiler.StepTraceAnnotation("train.step",
                                                      step_num=step),
                     "train.step")
