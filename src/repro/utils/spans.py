"""Spans and counters: the program's one timing mechanism.

``with span(name, counters, **kw):`` does two things:

* it enters ``jax.profiler.TraceAnnotation(name, **kw)``, so the span is
  written into the profiler's own trace, on the same clock as the device
  operations (with no profiler active that costs a flag check);
* it adds the span's ``perf_counter`` seconds and one count to the flat
  ``counters`` dict, under ``span_s.<name>`` and ``span_n.<name>``.

Counters are always on: a span costs two clock reads, the annotation's
check and two dict adds.  The span's own seconds stay readable as
``.s`` after the block, for callers that keep a timer of their own
(maintenance time, insert stall).  A span that a profiler session does
not enclose from start to end is left out of the trace (the profiler
records an annotation only if it was active at both ends), but always
counted.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation


def add(counters: dict, key: str, value: float) -> None:
    """Add ``value`` to the monotonic total ``counters[key]``."""
    counters[key] = counters.get(key, 0) + value


class span:
    """Context manager: a profiler annotation plus a counted wall time."""

    __slots__ = ("name", "counters", "kw", "s", "_t0", "_ann")

    def __init__(self, name: str, counters: dict, **kw):
        self.name = name
        self.counters = counters
        self.kw = kw
        self.s = 0.0

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self.name, **self.kw)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.s = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        add(self.counters, "span_s." + self.name, self.s)
        add(self.counters, "span_n." + self.name, 1)
