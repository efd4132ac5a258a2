"""The program's own spans in a traced slice, against the device's idle
time.

The serve pump writes its spans into the profiler's trace on the
device's clock (``repro.utils.spans``): ``serve.step`` while it works,
``serve.idle`` while it waits for a request, ``queue.window`` while it
holds a micro-batch open.  Between them they cover the pump's time, so
the device's idle time in the slice splits into the idle time under
each.  A trace of a program that writes no such spans reads nothing.
"""
from __future__ import annotations

from bench import trace_reduce

PUMP_SPANS = ("serve.step", "serve.idle", "queue.window")


def span_intervals(events, name: str) -> list[tuple[int, int]]:
    """``(start, end)`` of every host event named ``name``."""
    return [(s, s + d) for p, _, n, s, d in events
            if p.startswith("/host:") and n == name]


def idle_intervals(events, lo: int, hi: int) -> list[tuple[int, int]] | None:
    """The slice's intervals with no operation on the first device, or
    None where the trace holds no device operation."""
    ops = trace_reduce.device_ops(events, 1)
    if not ops:
        return None
    evs = next(iter(ops.values()))
    out, prev = [], lo
    for s, e in trace_reduce._merged(((s, s + d) for _, s, d in evs), lo, hi):
        if s > prev:
            out.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        out.append((prev, hi))
    return out


def overlap_ns(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> int:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_split(events, bounds) -> dict[str, float] | None:
    """Percent of the slice in which the device was idle, in all and
    under each pump span (``outside`` is the idle time under none), or
    None where the trace has no pump span or no device operation."""
    if not events or not bounds:
        return None
    lo, hi = bounds
    idle = idle_intervals(events, lo, hi)
    spans = {n: trace_reduce._merged(span_intervals(events, n), lo, hi)
             for n in PUMP_SPANS}
    if idle is None or not spans["serve.step"]:
        return None
    pct = 100.0 / (hi - lo)
    out = {"device_idle": pct * sum(e - s for s, e in idle)}
    for n, iv in spans.items():
        out[n] = pct * overlap_ns(idle, iv)
    out["outside"] = out["device_idle"] - sum(out[n] for n in PUMP_SPANS)
    return out


def span_share(r: dict, names: tuple[str, ...]) -> float | None:
    """Percent of the traced slice covered by the union of the program's
    spans named ``names``, or None where the trace holds no pump span
    (a program that writes no spans).  A span open when the profiler
    starts or stops is missing from the trace, so the share can read
    low by at most one span at each edge of the slice."""
    events, bounds = r.get("trace_events"), r.get("trace_bounds")
    if not events or not bounds or not span_intervals(events, "serve.step"):
        return None
    lo, hi = bounds
    iv = [x for n in names for x in span_intervals(events, n)]
    return 100.0 * sum(e - s for s, e in trace_reduce._merged(iv, lo, hi)) \
        / (hi - lo)


def host_bound_idle_share(r: dict) -> float | None:
    """Percent of the traced slice in which the device was idle while the
    pump was inside a ``serve.step``: idle time the host's own work
    holds the device back by."""
    split = idle_split(r.get("trace_events"), r.get("trace_bounds"))
    return None if split is None else split["serve.step"]
