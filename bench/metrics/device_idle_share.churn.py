"""Device: percent of a traced slice of the window with no operation on
the device."""
from bench.metrics._common import device_idle_share


def read(r):
    return device_idle_share(r)
