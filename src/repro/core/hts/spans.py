"""Host spans on the profiler's clock and on the program's own.

A :class:`Spans` opens each span as a ``jax.profiler.TraceAnnotation``, so
a captured trace shows it on the profiler's clock beside the device's
work, and appends it as a ``(name, start, end)`` row read from the
caller's clock, so the program's own records (``PopulationResult.
wall_us``, ``Server.report()``) come from the same span.  A call costs a
few microseconds with or without a profiler running (docs/API.md,
"Spans and scopes", gives the measured figures).
"""
from __future__ import annotations

import contextlib
import time

from jax.profiler import TraceAnnotation


class Spans:
    """Spans timed on ``clock`` (seconds; ``time.perf_counter`` unless
    given, e.g. the server's injectable clock)."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.rows: list = []         # (name, start, end), closing order

    @contextlib.contextmanager
    def __call__(self, name: str, **args):
        """One span; ``args`` become the trace event's arguments."""
        t0 = self.clock()
        with TraceAnnotation(name, **args):
            yield
        self.rows.append((name, t0, self.clock()))

    def seconds(self, name: str) -> float:
        """Total length of the spans named ``name``."""
        return sum(e - s for n, s, e in self.rows if n == name)

