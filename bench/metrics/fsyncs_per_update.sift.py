"""WAL: fsyncs in the window per update acknowledged in it, in the SIFT
control cell, where the update tail is read per layer."""
from bench.metrics._common import fsyncs_per_update


def read(r):
    return fsyncs_per_update(r)
