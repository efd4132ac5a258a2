"""End to end: the update tail, p90 in ms (`_common.update_p90_ms`)."""
from bench.metrics._common import update_p90_ms


def read(r):
    return update_p90_ms(r)
