"""Host spans and counters, recorded by the benchmark around its calls
into the program. Kept in memory; with tracing on, each span is also
written into the profiler's own trace (`bench:<name>`), so that a device
idle gap can be named by the span open at the time, on one clock."""

from __future__ import annotations

import time
from contextlib import contextmanager


class Recorder:
    def __init__(self, annotate: bool = False):
        self.spans: list[tuple[str, float, float]] = []
        self.annotate = annotate
        self.clock = time.perf_counter

    @contextmanager
    def span(self, name: str):
        note = None
        if self.annotate:
            import jax

            note = jax.profiler.TraceAnnotation("bench:" + name)
            note.__enter__()
        t0 = self.clock()
        try:
            yield
        finally:
            self.spans.append((name, t0, self.clock()))
            if note is not None:
                note.__exit__(None, None, None)
