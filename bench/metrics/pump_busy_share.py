"""Pump: percent of a traced slice of the window that the serve pump
spent inside its `serve.step` spans (a batch, or the idle branch's
readbacks, ack and maintenance slot)."""
from bench.metrics._spans import span_share


def read(r):
    return span_share(r, ("serve.step",))
