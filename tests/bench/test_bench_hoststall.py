"""The stall watch times a thread that holds the GIL, counts only what
began inside the window, and leaves nothing running when stopped."""
from __future__ import annotations

import gc
import random
import threading
import time

from bench import hoststall


def _hold_gil(x, go, took):
    go.wait()
    t = time.perf_counter()
    sorted(x)          # one C call: the GIL is not released until it returns
    took.append(time.perf_counter() - t)


def test_stall_is_timed(tmp_path):
    x = [random.random() for _ in range(2_000_000)]
    w = hoststall.StallWatch().start()
    go, took = threading.Event(), []
    th = threading.Thread(target=_hold_gil, args=(x, go, took), name="holder")
    th.start()
    t0 = time.perf_counter()
    go.set()
    th.join()
    elapsed = time.perf_counter() - t0
    time.sleep(0.1)
    w.stop()
    s = w.summary(0.0, float("inf"))
    if took[0] > 0.3:
        assert s["stalls_over_100ms"] >= 1, s
    assert s["stall_max_ms"] <= (elapsed + 0.1) * 1e3, s
    assert s["proc_cpu_s"] > 0


def test_summary_counts_only_the_window():
    w = hoststall.StallWatch()
    w.sys0 = w.sys1 = {"proc_cpu_s": 1.0}
    # (t, wall s, process CPU s, steal s) of each gap
    w.gaps = [(0.5, 0.3, 0.0, 0.0), (1.0, 0.12, 0.01, 0.0),
              (1.5, 0.06, 0.06, 0.0), (2.0, 0.9, 0.0, 0.0)]
    w.gcs = [(0.5, 0.2, 2), (1.2, 0.03, 0), (1.8, 0.05, 2)]
    s = w.summary(1.0, 2.0)
    assert s["stall_max_ms"] == 120.0
    assert s["stall_max_cpu_ms"] == 10.0
    assert s["stalls_over_100ms"] == 1
    assert abs(s["stall_total_ms"] - 180.0) < 1e-9
    assert s["gc_max_ms"] == 50.0 and s["gc_full"] == 1
    assert s["proc_cpu_s"] == 0.0


def test_stop_leaves_nothing_running():
    before = {t.ident for t in threading.enumerate()}
    callbacks = len(gc.callbacks)
    w = hoststall.StallWatch(tick_s=0.005).start()
    time.sleep(0.05)
    w.stop()
    assert {t.ident for t in threading.enumerate()} <= before
    assert len(gc.callbacks) == callbacks
