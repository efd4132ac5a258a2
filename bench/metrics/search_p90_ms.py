"""p90 of every search scheduled in the window, from its scheduled
arrival to its completion, in ms.  (The tail beyond it is set by how many
searches a whole-process freeze of about 100 ms catches: PERF.md.)"""
from bench.metrics._common import percentile


def read(r):
    return percentile(r["lat_ms"]["search"], 90)
