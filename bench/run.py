#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets up (data from the seed, index build through ``spfresh.open``,
snapshot, warm-up of every bucket shape), drives the open-loop window for
``--seconds``, checks every answer against the plain reference, and
prints one JSON object as the last line of standard output.  With
``--trace 0`` it holds the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics and the device's busy time from a profiler trace.
The numbers compared for ``correct`` are printed with their limits as the
last lines of standard error and under ``checks``, last in the object.

Exits non-zero, printing no result, when JAX finds no TPU (or fewer chips
than the cell asks for) or the program under test is missing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # a fatal signal (a crash below Python) prints every thread's stack
    faulthandler.enable(all_threads=True)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    try:
        import spfresh  # noqa: F401 — the system under test
    except ImportError as e:
        print(f"bench: the program under test is missing: {e}",
              file=sys.stderr)
        return 2
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except harness.NoAccelerator as e:
        print(f"bench: {e}; the benchmark runs only on the chip",
              file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
