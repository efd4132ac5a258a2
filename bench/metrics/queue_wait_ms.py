"""Batcher: mean milliseconds a search row waited in the queue, from its
enqueue to the pop of its micro-batch, over the window (the queue's own
counters `queue.wait_s.search` and `queue.rows.search`, delta over the
window)."""


def read(r):
    d = r["delta"]
    rows = d.get("queue.rows.search", 0)
    return 1e3 * d["queue.wait_s.search"] / rows if rows else None
