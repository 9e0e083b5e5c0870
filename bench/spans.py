"""Host spans of the run loop.

While a trace is being taken each span is a
``jax.profiler.TraceAnnotation`` named ``bench.<name>``, so the trace
reducer can name every device idle gap by what the host was doing in
it; otherwise a span costs nothing.
"""
from __future__ import annotations

import contextlib

import jax

PREFIX = "bench."


class Spans:
    def __init__(self):
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        if self.tracing:
            with jax.profiler.TraceAnnotation(PREFIX + name):
                yield
        else:
            yield
