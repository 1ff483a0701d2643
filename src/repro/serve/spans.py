"""The serve engine's host spans and counters (DESIGN.md §7.2).

``Spans.span(name)`` opens a ``jax.profiler.TraceAnnotation``, so that under
an active profiler the span lands in the trace beside the device's planes,
on the same clock; it also adds the span's duration to ``totals[name]``
(``[calls, seconds]``), which is kept whether or not a profiler runs.
Without a profiler an annotation costs a fraction of a microsecond.

``Spans.listening()`` adds, while it is entered, what happens between the
engine's own spans: every XLA compile (a load from the persistent
compilation cache included) to ``compiles`` and ``compile_s``, and every
collection of the garbage collector as an ``engine.gc`` span.
"""

from __future__ import annotations

import contextlib
import gc
import time

import jax

#: what JAX records around each backend compile (or cache load)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
GC_SPAN = "engine.gc"


class Spans:
    def __init__(self):
        self.totals: dict[str, list] = {}   # name -> [calls, seconds]
        self.compiles = 0
        self.compile_s = 0.0

    def add(self, name: str, seconds: float) -> None:
        entry = self.totals.setdefault(name, [0, 0.0])
        entry[0] += 1
        entry[1] += seconds

    @contextlib.contextmanager
    def span(self, name: str):
        t = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.add(name, time.perf_counter() - t)

    def _on_compile(self, event: str, start: float, end: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += end - start

    @contextlib.contextmanager
    def listening(self):
        """Count compiles and garbage collections until the block exits."""
        open_gc = []   # [annotation, start] while a collection runs

        def on_gc(phase: str, info: dict) -> None:
            if phase == "start":
                note = jax.profiler.TraceAnnotation(GC_SPAN)
                note.__enter__()
                open_gc[:] = [note, time.perf_counter()]
            elif open_gc:
                note, t = open_gc
                note.__exit__(None, None, None)
                self.add(GC_SPAN, time.perf_counter() - t)
                open_gc.clear()

        jax.monitoring.register_event_time_span_listener(self._on_compile)
        gc.callbacks.append(on_gc)
        try:
            yield
        finally:
            gc.callbacks.remove(on_gc)
            jax.monitoring.unregister_event_time_span_listener(self._on_compile)
