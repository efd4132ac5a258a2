"""Host stalls inside the window: how long the process stopped running
Python.

A heartbeat thread wakes every ``tick_s``; garbage collections are timed
by a ``gc`` callback.  The heartbeat's own lateness, with the process's
CPU time over each gap, tells a thread that holds the GIL (CPU time runs
on) from a process that is not scheduled at all (it does not); the CPUs'
steal time over the gap, and the cgroup's throttling and the kernel's
pressure stall totals over the window, say whether the machine was
running something else.  Nothing here reads another thread's stack: a
watchdog that dumps the stacks of running threads (``faulthandler``'s
``dump_traceback_later``) walks their frames without the GIL and can
crash the process.
"""
from __future__ import annotations

import gc
import os
import threading
import time

STALL_MS = 50.0          # heartbeat gaps longer than this are recorded
# where the kernel reports CPU throttling of this process's cgroup, and
# the time tasks waited for a CPU, for memory or for I/O (pressure stall
# information); whichever exist are read at the start and end of a window
CPU_STAT = ("/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat",
            "/sys/fs/cgroup/cpu,cpuacct/cpu.stat")
PRESSURE = {"cpu": "/proc/pressure/cpu", "memory": "/proc/pressure/memory",
            "io": "/proc/pressure/io"}


def _read_kv(path: str) -> dict[str, int]:
    try:
        with open(path) as f:
            return {k: int(v) for k, v in (line.split()[:2] for line in f
                                           if len(line.split()) >= 2)
                    if v.isdigit()}
    except OSError:
        return {}


def steal_ms() -> float:
    """Time the hypervisor ran something else on this machine's CPUs,
    summed over the CPUs (``/proc/stat``, clock ticks of 10 ms); 0 where
    the kernel does not say."""
    try:
        with open("/proc/stat") as f:
            return int(f.readline().split()[8]) * 10.0
    except (OSError, IndexError, ValueError):
        return 0.0


def system_counters() -> dict[str, float]:
    """Throttled time of the cgroup (ms), how often it was throttled, the
    CPUs' steal time (ms) and the pressure stall totals (ms, ``some``
    line), where the kernel has them; the process's CPU seconds."""
    out: dict[str, float] = {"proc_cpu_s": time.process_time()}
    for path in CPU_STAT:
        st = _read_kv(path)
        if st:
            if "throttled_usec" in st:          # cgroup v2
                out["throttled_ms"] = st["throttled_usec"] / 1e3
            elif "throttled_time" in st:        # cgroup v1, ns
                out["throttled_ms"] = st["throttled_time"] / 1e6
            if "nr_throttled" in st:
                out["nr_throttled"] = float(st["nr_throttled"])
            break
    out["steal_ms"] = steal_ms()
    for kind, path in PRESSURE.items():
        try:
            with open(path) as f:
                some = f.readline().split()
        except OSError:
            continue
        total = [x for x in some if x.startswith("total=")]
        if total:
            out[f"{kind}_pressure_ms"] = int(total[0][6:]) / 1e3
    return out


def machine() -> dict[str, str]:
    """The CPUs this process may use and its cgroup's CPU limit."""
    out = {"cpus": str(os.cpu_count()),
           "affinity": str(len(os.sched_getaffinity(0)))}
    for path in ("/sys/fs/cgroup/cpu.max", "/sys/fs/cgroup/cpu/cpu.cfs_quota_us"):
        try:
            with open(path) as f:
                out["cpu_limit"] = f.read().strip().replace(" ", "/")
            break
        except OSError:
            continue
    return out


class StallWatch:
    def __init__(self, *, tick_s: float = 0.02):
        self.tick_s = tick_s
        # (t, wall s, process CPU s, steal s summed over CPUs) of each gap
        self.gaps: list[tuple[float, float, float, float]] = []
        self.gcs: list[tuple[float, float, int]] = []     # (t, s, generation)
        self._stop = threading.Event()
        self._gc_t0 = 0.0
        self._thread = None
        self.sys0: dict[str, float] = {}
        self.sys1: dict[str, float] = {}

    def _gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            t = time.perf_counter()
            self.gcs.append((self._gc_t0, t - self._gc_t0,
                             int(info["generation"])))

    def _beat(self) -> None:
        last, cpu, steal = time.perf_counter(), time.process_time(), steal_ms()
        while not self._stop.is_set():
            time.sleep(self.tick_s)
            now, cpu_now, steal_now = (time.perf_counter(), time.process_time(),
                                       steal_ms())
            late = now - last - self.tick_s
            if late * 1e3 > STALL_MS:
                self.gaps.append((last, late, cpu_now - cpu,
                                  (steal_now - steal) / 1e3))
            last, cpu, steal = now, cpu_now, steal_now

    def start(self) -> "StallWatch":
        self.sys0 = system_counters()
        gc.callbacks.append(self._gc)
        self._thread = threading.Thread(target=self._beat, name="stall-watch",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self.sys1 = system_counters()
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._gc)

    def summary(self, lo: float, hi: float) -> dict:
        """Stalls and collections that began inside ``[lo, hi)``."""
        gaps = [g for g in self.gaps if lo <= g[0] < hi]
        gcs = [g for g in self.gcs if lo <= g[0] < hi]
        worst = max(gaps, key=lambda g: g[1], default=(0.0, 0.0, 0.0, 0.0))
        return {
            "stall_max_ms": worst[1] * 1e3,
            "stall_max_cpu_ms": worst[2] * 1e3,
            "stall_max_steal_ms": worst[3] * 1e3,
            "stall_steal_ms": sum(g[3] for g in gaps) * 1e3,
            "stalls_over_100ms": sum(1 for g in gaps if g[1] > 0.1),
            "stall_total_ms": sum(g[1] for g in gaps) * 1e3,
            "gc_max_ms": max((g[1] for g in gcs), default=0.0) * 1e3,
            "gc_full": sum(1 for g in gcs if g[2] == 2),
            **{k: self.sys1[k] - self.sys0[k] for k in self.sys0
               if k in self.sys1},
        }
