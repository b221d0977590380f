"""Host spans of the serving engine, on the profiler's clock.

A :class:`Span` opens a ``jax.profiler.TraceAnnotation``: under a profiler
trace it lands in the ``.xplane.pb`` host plane on the device trace's clock,
so each idle gap of the device can be named by what the engine was doing;
with no trace running it is a TraceMe that records nothing.  A span given
counter names also adds its duration, read on the engine's injectable clock,
to each of those counters of ``stats``, so one timer measures each interval
and the counters stay testable under a fake clock.  A span adds no device
synchronisation: the wait spans wrap the syncs the engine already makes.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


class Span:
    """``with Span(name, clock, stats, counters) as sp:`` — ``sp.s`` holds
    the duration once the block exits (when ``counters`` is non-empty)."""

    __slots__ = ("name", "clock", "stats", "counters", "t0", "s", "_ann")

    def __init__(self, name: str, clock, stats: dict, counters=()):
        self.name = name
        self.clock = clock
        self.stats = stats
        self.counters = counters
        self.s = 0.0

    def __enter__(self) -> "Span":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        if self.counters:
            self.t0 = self.clock()
        return self

    def __exit__(self, *exc):
        if self.counters:
            self.s = self.clock() - self.t0
            for k in self.counters:
                self.stats[k] += self.s
        self._ann.__exit__(*exc)
        return False
