"""Kernel: percent of the batched scan's page grid that held a distinct
page some real query row probed, over the window's dispatches (the
scan's own counters `scan.pages_unique` and `scan.pages_grid`, delta
over the window)."""


def read(r):
    d = r["delta"]
    grid = d.get("scan.pages_grid", 0)
    return 100.0 * d["scan.pages_unique"] / grid if grid else None
