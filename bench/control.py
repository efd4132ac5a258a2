#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program's and the controls'.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, one whole run of the cell (set-up, a window at the cell's
own load, the checks), in one process.  Beside the program's compared
numbers it prints those of three controls put in the program's place over
the same queries and live sets:

* ``control_int4`` — the reference computed on int4 (the top 4 bits of
  each int8 value): the precision step below the configuration's;
* ``control_stale`` — the reference over the build's set, as if every
  insert and delete step had returned its state unchanged;
* ``control_refuse`` — the reference that refuses every insert (answered
  "not written") and applies every delete.

The benchmark's own runs never compute these.  Lines also go to
``chiprun_out/control-<cell>.jsonl``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", f"control-{args.workload}.jsonl")
    t0 = T_START
    for seed in (int(x) for x in args.seeds.split(",")):
        try:
            out = harness.run_cell(args.workload, seed, args.seconds, False,
                                   t_start=t0, control=True)
        except harness.NoAccelerator as e:
            print(f"control: {e}", file=sys.stderr)
            return 1
        row = {"workload": args.workload, "seed": seed,
               "correct": out["correct"], "failed": out["failed"],
               "program": {k: c["value"] for k, c in out["checks"].items()},
               "control_int4": out["control_int4"],
               "control_stale": out["control_stale"],
               "control_refuse": out["control_refuse"],
               "metrics": {k: m["value"] for k, m in out["metrics"].items()}}
        print(json.dumps(row), flush=True)
        with open(path, "a") as f:
            f.write(json.dumps(row) + "\n")
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
