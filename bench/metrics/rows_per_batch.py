"""Batcher: request rows per micro-batch dispatched in the window
(the queue's own counters, delta over the window)."""


def read(r):
    d = r["delta"]
    return d["rows"] / d["batches"] if d["batches"] else None
