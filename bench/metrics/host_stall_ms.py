"""Host: milliseconds of the window in which the process ran no Python
(heartbeat gaps over 50 ms, `bench/hoststall.py`), summed."""


def read(r):
    host = r.get("host")
    return None if host is None else float(host["stall_total_ms"])
