"""Maintenance: percent of the window the engine spent in
Local-Rebuilder rounds (its maintenance time_s, delta over the window)."""


def read(r):
    return 100.0 * r["delta"]["maint_time_s"] / r["window_s"]
