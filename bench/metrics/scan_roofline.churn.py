"""Kernel: the batched posting scan's share of its roofline, in percent,
on full batches of held-out queries traced after the window."""
from bench.metrics._common import scan_roofline


def read(r):
    return scan_roofline(r)
