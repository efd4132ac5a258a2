"""Whole benchmark runs of a 4-shard configuration through
``harness.run_cell``: ``bench_tiny``'s cut of the SPACEV shard with
``shards: {"n_shards": 4}``, one shard per device, a 2 s window.

    python tests/bench/sharded_script.py --cpu [case ...]   # 4 CPU devices
    python tests/bench/sharded_script.py [case ...]         # a 4-chip host

JAX fixes its device count when it starts, so the CPU devices are made
here, in a process of their own.  Cases: ``sound``; ``handle_shifted``,
the harness's map from rows to the backend's handles moved by one shard;
``raw_handles``, answers read as the backend's handles without the map.
Prints one JSON line per case: ``{"case", "correct", "checks",
"device"}``.
"""
from __future__ import annotations

import os
import sys
import tempfile

CPU = "--cpu" in sys.argv
if CPU:
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                               + os.environ.get("XLA_FLAGS", ""))
    os.environ["JAX_PLATFORMS"] = "cpu"

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src"), HERE]

import copy  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402

from bench import harness, registry  # noqa: E402
from bench_tiny import tiny_cell  # noqa: E402

CELL = "spfresh1b-spacev-shard.churn"
SHARDS = 4
SEED = 2**31 + 17
CASES = ("sound", "handle_shifted", "raw_handles")


def sharded_cell():
    """``(bench, config, traffic)``: the tiny cut with four shards, and a
    copy of ``BENCHMARK.json`` whose cell asks for four chips."""
    config, traffic = tiny_cell(CELL)
    config["shards"] = {"n_shards": SHARDS}
    bench = copy.deepcopy(registry.load())
    registry.workload(bench, CELL)["chips"] = SHARDS
    return bench, config, traffic


def plant(case: str, config: dict):
    """Break the harness's handle map for ``case``; returns the undo."""
    Session = harness.Session
    if case == "handle_shifted":
        orig = Session._map
        span = SHARDS * int(config["index"]["num_vectors_cap"])
        step = int(config["index"]["num_vectors_cap"])

        def shifted(self, rows, handles):
            h = np.asarray(handles, np.int64)
            orig(self, rows, np.where(h >= 0, (h + step) % span, h))
        Session._map = shifted
        return lambda: setattr(Session, "_map", orig)
    if case == "raw_handles":
        orig = Session.rows
        Session.rows = lambda self, handles: np.asarray(handles, np.int64)
        return lambda: setattr(Session, "rows", orig)
    return lambda: None


def main(cases) -> int:
    bench, config, traffic = sharded_cell()
    with tempfile.TemporaryDirectory() as cache:
        if CPU:
            import jax

            # the runs build the same programs: compile them once
            jax.config.update("jax_compilation_cache_dir", cache)
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                              -1)
        for case in cases:
            run_case(case, bench, config, traffic)
    return 0


def run_case(case, bench, config, traffic) -> None:
    undo = plant(case, config)
    try:
        with tempfile.TemporaryDirectory() as tmp:
            out = harness.run_cell(
                CELL, SEED, 2.0, False, t_start=time.perf_counter(),
                bench=bench, config=config, traffic=traffic,
                require_tpu=not CPU, workdir=pathlib.Path(tmp) / "root")
    finally:
        undo()
    print(json.dumps({"case": case, "correct": out["correct"],
                      "failed": out["failed"],
                      "checks": {k: c["value"]
                                 for k, c in out["checks"].items()},
                      "device": out["device"]}), flush=True)


if __name__ == "__main__":
    picked = [a for a in sys.argv[1:] if not a.startswith("--")]
    sys.exit(main(picked or CASES))
