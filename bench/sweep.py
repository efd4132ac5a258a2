#!/usr/bin/env python3
"""Knee sweep: one service, a window at each offered rate, ascending.

    python3 bench/sweep.py --workload <cell> --rates 25,50,100 --seconds 10 \
        [--traffic <mix> ...] [--seed n]

Sets the cell up once (as ``run.py`` does), then for each traffic mix
given (default: the cell's own) and each rate drives a window of
``--seconds`` at that offered rate, waits for every request, and prints
one JSON line: offered and completed rates, p50/p99 per kind, the queue's
depth at the close, the maintenance backlog and the generator's
lateness.  The knee of a mix is the highest offered rate whose searches
and updates complete at the offered rate, with no queue left growing at
the close.  The sweep stops a mix once completions fall below 80% of the
offered rate.  Lines also go to ``chiprun_out/sweep-<cell>.jsonl``.
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
    ap.add_argument("--traffic", action="append", default=None)
    ap.add_argument("--rates", action="append", required=True,
                    help="comma-separated rates, one list per --traffic")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import numpy as np

    from bench import harness, registry
    from bench.traffic import DELETE, INSERT, SEARCH, make_schedule

    bench = registry.load()
    cell = registry.workload(bench, args.workload)
    try:
        devs = harness.require_chips(int(cell["chips"]))
    except harness.NoAccelerator as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 1
    harness.enable_cache()
    config = registry.config(bench, cell["config"])
    mixes = [registry.traffic(t) for t in (args.traffic or [cell["traffic"]])]
    plans = []
    for mix, rates in zip(mixes, args.rates):
        for i, r in enumerate(float(x) for x in rates.split(",")):
            plans.append((mix, r, make_schedule(mix, args.seed + i,
                                                args.seconds, rate_per_s=r)))
    total = {k: sum(p[2].count(k) for p in plans)
             for k in (SEARCH, INSERT, DELETE)}
    s = harness.Session(config, mixes[0], args.seed, n_search=total[SEARCH],
                        n_insert=total[INSERT], n_delete=total[DELETE],
                        workdir=harness.OUT / "root" / "sweep")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out_path = os.path.join(ROOT, "chiprun_out", f"sweep-{args.workload}.jsonl")
    try:
        opened = s.open()
        warm = s.warm_up()
        harness.log(f"sweep setup: {opened} warm_s={warm:.3f} "
                    f"total_s={time.perf_counter() - T_START:.3f} "
                    f"device={devs[0].device_kind}")
        stopped = set()
        with open(out_path, "a") as f:
            for mix, rate, sched in plans:
                if id(mix) in stopped:
                    continue
                win = s.run_window(sched, args.seconds)
                row = {"workload": args.workload, "rate": rate,
                       "shares": mix["shares"], "seconds": args.seconds}
                done = {"search": 0, "update": 0}
                lat = {"search": [], "update": []}
                for j, tk in enumerate(win.tickets):
                    key = "search" if win.op[j] == SEARCH else "update"
                    if not win.answered[j]:
                        continue
                    lat[key].append((tk.t_done - win.t_sched[j]) * 1e3)
                    done[key] += tk.t_done <= win.close
                n = len(sched.t)
                row["offered_per_s"] = n / args.seconds
                row["completed_per_s"] = sum(done.values()) / args.seconds
                for key, v in lat.items():
                    if v:
                        row[f"{key}_p50_ms"] = float(np.percentile(v, 50))
                        row[f"{key}_p99_ms"] = float(np.percentile(v, 99))
                row["not_done_at_close"] = n - sum(done.values())
                c0, c1 = win.counters0, win.counters1
                row["rows_per_batch"] = ((c1["rows"] - c0["rows"])
                                         / max(1, c1["batches"] - c0["batches"]))
                row["maint_busy_share"] = (c1["maint_time_s"]
                                           - c0["maint_time_s"]) / args.seconds
                row["maint_slots"] = c1["maint_slots"] - c0["maint_slots"]
                row["backlog_end"] = c1["backlog"]
                row["compiles"] = win.compiles
                row["lateness_ms"] = harness.lateness_ms(win)
                print(json.dumps(row), flush=True)
                f.write(json.dumps(row) + "\n")
                if row["completed_per_s"] < 0.8 * row["offered_per_s"]:
                    stopped.add(id(mix))
    finally:
        s.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
