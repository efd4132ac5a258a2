"""Pump: percent of a traced slice of the window in which the device was
idle while the serve pump was inside a `serve.step` span."""
from bench.metrics._spans import host_bound_idle_share


def read(r):
    return host_bound_idle_share(r)
