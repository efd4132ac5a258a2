"""Arithmetic shared by the metric readers in this directory."""
from __future__ import annotations

import numpy as np

from bench import peaks, trace_reduce

# The batched posting scan's Pallas kernel, as its device operations are
# named in the profiler trace.
SCAN_KERNEL = "scan_batched_topk"


def percentile(values, q: float) -> float | None:
    v = np.asarray(values, np.float64)
    return float(np.percentile(v, q)) if v.size else None


def fsyncs_per_update(r: dict) -> float | None:
    """WAL fsyncs in the window per update acknowledged in it."""
    n = r["updates_acked_in_window"]
    return r["delta"]["fsyncs"] / n if n else None


def update_p90_ms(r: dict) -> float | None:
    """p90 of every acknowledged insert and delete scheduled in the window
    (a refused insert is counted apart, under `insert_refused`), from its
    scheduled arrival to its acknowledgement after the WAL fsync, in ms."""
    return percentile(r["lat_ms"]["update"], 90)


def scan_roofline(r: dict) -> float | None:
    """Percent of the least time the cell's chips could take for the
    traced batches' scan work (the larger of needed bytes over their HBM
    bandwidth and needed operations over their peak FLOP/s, each one
    chip's peak times the chips) in the scan kernel's device time
    averaged over those chips.  The work is summed over the shards."""
    roof = r.get("roof")
    if not roof:
        return None
    chips = r.get("chips", 1)
    kernel, n = trace_reduce.kernel_s(roof["events"], SCAN_KERNEL, chips)
    if n == 0 or kernel <= 0:
        return None
    pk = peaks.peaks(r["device_kind"])
    least = max(roof["bytes"] / (pk["hbm_bytes_per_s"] * chips),
                roof["flops"] / (pk["flops_bf16"] * chips))
    return 100.0 * least / kernel


def device_idle_share(r: dict) -> float | None:
    """Percent of the traced slice of the window in which no operation ran
    on the device, averaged over the cell's chips."""
    ev, bounds = r.get("trace_events"), r.get("trace_bounds")
    if not ev or not bounds:
        return None
    lo, hi = bounds
    busy = trace_reduce.busy_s(ev, lo, hi, r.get("chips", 1))
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / ((hi - lo) / 1e9))
