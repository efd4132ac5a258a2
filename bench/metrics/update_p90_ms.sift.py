"""WAL: the update tail of the SIFT control cell, p90 in ms, read per
layer there because its runs spread too widely for an end-to-end bound
(`_common.update_p90_ms`)."""
from bench.metrics._common import update_p90_ms


def read(r):
    return update_p90_ms(r)
