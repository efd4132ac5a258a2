"""From a profiler trace to the device numbers: busy time, kernel time,
and the breakdown of device operations and idle gaps.

``load_xplane`` flattens JAX's ``.xplane.pb`` into plain event tuples
``(plane, line, name, start_ns, dur_ns)``; every reduction below works on
that list, so a small recorded trace can be checked into the tests.

* Busy time is the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped
  to the traced slice and averaged over the chips used.
* Kernel time is the sum of the durations of the device operations whose
  name contains the kernel's name.
* Each idle gap is named by what the host was doing in it: the shortest
  host event that covers the gap's middle.
"""
from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
SLICE_SPAN = "bench.trace_slice"
HOST_MIN_NS = 5_000          # host events shorter than this are dropped


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_xplane(path: str) -> list[tuple]:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name != OP_LINE:
                continue
            for e in line.events:
                dur = int(e.duration_ns)
                if device or dur >= HOST_MIN_NS or e.name == SLICE_SPAN:
                    out.append((plane.name, line.name, e.name,
                                int(e.start_ns), dur))
    return out


def op_name(name: str) -> str:
    """A device op's short name: its HLO instruction name, without the
    instruction text the trace carries after it."""
    return name.split(" = ", 1)[0].lstrip("%")


def device_ops(events, chips: int = 1) -> dict[str, list[tuple]]:
    """``{plane: [(name, start, dur), ...]}`` for the first ``chips``
    devices that have operations."""
    by_plane: dict[str, list[tuple]] = {}
    for plane, line, name, start, dur in events:
        if plane.startswith(DEVICE_PREFIX) and line == OP_LINE:
            by_plane.setdefault(plane, []).append((name, start, dur))
    keep = sorted(by_plane)[:chips]
    return {p: sorted(by_plane[p], key=lambda e: e[1]) for p in keep}


def slice_bounds(events) -> tuple[int, int] | None:
    spans = [(s, s + d) for _, _, n, s, d in events if n == SLICE_SPAN]
    return (min(s for s, _ in spans), max(e for _, e in spans)) \
        if spans else None


def _merged(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(events, lo: int, hi: int, chips: int = 1) -> float:
    """Union of device-op intervals inside ``[lo, hi)``, averaged over the
    chips used, in seconds."""
    ops = device_ops(events, chips)
    if not ops:
        return 0.0
    total = 0
    for evs in ops.values():
        total += sum(e - s for s, e in _merged(
            ((st, st + d) for _, st, d in evs), lo, hi))
    return total / len(ops) / 1e9


def kernel_s(events, pattern: str, chips: int = 1) -> tuple[float, int]:
    """Summed device time of the operations whose name holds ``pattern``
    (averaged over chips) and how many such events there were."""
    ops = device_ops(events, chips)
    tot, n = 0, 0
    for evs in ops.values():
        for name, _, dur in evs:
            if pattern in name:
                tot += dur
                n += 1
    return (tot / len(ops) / 1e9 if ops else 0.0), n


def breakdown(events, lo: int, hi: int, top: int = 10) -> dict:
    """The device operations that took most time and the longest idle
    gaps, each gap named by the host's activity at its middle."""
    ops = device_ops(events, 1)
    if not ops:
        return {"device_ops": [], "idle_gaps": []}
    evs = next(iter(ops.values()))
    per_op: dict[str, int] = {}
    for name, s, d in evs:
        if s + d > lo and s < hi:
            per_op[op_name(name)] = per_op.get(op_name(name), 0) + d
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    busy = _merged(((s, s + d) for _, s, d in evs), lo, hi)
    gaps, prev = [], lo
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [(n, s, d) for p, _, n, s, d in events
            if p.startswith("/host:") and n != SLICE_SPAN]
    named = []
    for s, e in gaps[:top]:
        mid = (s + e) // 2
        cover = [(d, n) for n, hs, d in host if hs <= mid < hs + d]
        named.append([min(cover)[1] if cover else "no host event",
                      (e - s) / 1e9])
    return {"device_ops": [[n, d / 1e9] for n, d in top_ops],
            "idle_gaps": named}
