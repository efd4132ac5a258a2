"""WAL: fsyncs in the window per update acknowledged in it (the cells
that bound `update_p90_ms`)."""
from bench.metrics._common import fsyncs_per_update


def read(r):
    return fsyncs_per_update(r)
