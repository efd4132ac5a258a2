"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]
    PYTHONPATH=src python -m benchmarks.run --json BENCH_search.json

``--json PATH`` runs the search data-path benchmark and writes a
machine-readable report (p50/p99 search latency + modeled scan GB/query
for the oracle vs per-query vs batch-dedup Pallas schedules) so the perf
trajectory is tracked across PRs.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback


BENCHES = [
    ("shift", "benchmarks.bench_shift"),                 # Fig. 2 / Fig. 10
    ("scenarios", "benchmarks.bench_scenarios"),         # serving gauntlet
    ("update_sim", "benchmarks.bench_update_sim"),       # Fig. 7 (workload A/B)
    ("stress", "benchmarks.bench_stress"),               # Fig. 9 (workload C)
    ("reassign_range", "benchmarks.bench_reassign_range"),  # Fig. 11
    ("pipeline", "benchmarks.bench_pipeline_balance"),   # Fig. 12
    ("serve_async", "benchmarks.bench_serve_async"),     # open-loop tails
    ("replicas", "benchmarks.bench_replicas"),           # read replicas
    ("rebuild_cost", "benchmarks.bench_rebuild_cost"),   # Table 1
    ("maintenance", "benchmarks.bench_maintenance"),     # batched rounds
    ("recovery", "benchmarks.bench_recovery"),           # §4.4 durability
    ("kernels", "benchmarks.bench_kernels"),             # hot-path micro
    ("search_path", "benchmarks.bench_search_path"),     # scan data paths
    ("roofline", "benchmarks.roofline_report"),          # §Roofline summary
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow); default quick")
    ap.add_argument("--dry", action="store_true",
                    help="import smoke: load every bench module, run nothing")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a machine-readable report to PATH and exit")
    ap.add_argument("--report",
                    choices=["auto", "search", "maintenance", "recovery",
                             "scenarios", "serve", "replicas"],
                    default="auto",
                    help="which --json report to write; 'auto' picks "
                         "maintenance for paths containing 'update'/'maint', "
                         "recovery for 'recover', scenarios for "
                         "'scenario', replicas for 'replica', serve for "
                         "'serve', else search")
    args = ap.parse_args()
    if not args.dry:
        from repro.utils.compile_cache import enable_compile_cache

        enable_compile_cache()

    if args.json:
        import os

        base = os.path.basename(args.json).lower()
        which = args.report
        if which == "auto":
            if "update" in base or "maint" in base:
                which = "maintenance"
            elif "recover" in base:
                which = "recovery"
            elif "scenario" in base:
                which = "scenarios"
            elif "replica" in base:
                which = "replicas"
            elif "serve" in base:
                which = "serve"
            else:
                which = "search"
        if which == "scenarios":
            from benchmarks.bench_scenarios import run_json

            report = run_json(quick=not args.full)
            with open(args.json, "w") as f:
                json.dump(report, f, indent=2)
            shift = report["scenarios"]["shift"]
            print(f"# wrote {args.json}: shift drift_minus_size="
                  f"{shift['drift_minus_size']:+.3f} at "
                  f"jobs_per_round={shift['jobs_per_round']}")
            return
        if which == "replicas":
            from benchmarks.bench_replicas import run_json

            report = run_json(quick=not args.full)
            with open(args.json, "w") as f:
                json.dump(report, f, indent=2)
            s = report["summary"]
            print(f"# wrote {args.json}: "
                  f"read_scaling_2r={s['read_scaling_2r']:.2f}x (modeled) "
                  f"ack_overhead={s['ack_overhead_frac'] * 100:+.1f}% "
                  f"parity={s['bit_identical_at_equal_seqno']}")
            return
        if which == "serve":
            from benchmarks.bench_serve_async import run_json

            report = run_json(quick=not args.full)
            with open(args.json, "w") as f:
                json.dump(report, f, indent=2)
            s = report["summary"]
            print(f"# wrote {args.json}: "
                  f"search_p99 sync={s['sync_search_p99_ms']:.1f}ms "
                  f"async={s['async_search_p99_ms']:.1f}ms "
                  f"({s['search_p99_reduction_x']:.2f}x) "
                  f"overlap_frac={s['async_overlap_frac']:.2f}")
            return
        if which == "recovery":
            from benchmarks.bench_recovery import run_json

            report = run_json(quick=not args.full)
            with open(args.json, "w") as f:
                json.dump(report, f, indent=2)
            rec = report["recovery"]
            print(f"# wrote {args.json}: "
                  f"replayed_rows_s={rec['replayed_rows_s']:.0f} "
                  f"recover_open_s={rec['recover_open_s']:.2f}s "
                  f"snapshot_write_mb_s={report['snapshot']['write_mb_s']:.0f}")
            return
        if which == "maintenance":
            from benchmarks.bench_maintenance import run_json

            report = run_json(quick=not args.full)
            with open(args.json, "w") as f:
                json.dump(report, f, indent=2)
            sp = report["round_speedup_vs_step"]
            stall = report["insert_stall"]["stall_reduction"]
            print(f"# wrote {args.json}: round_speedup_vs_step="
                  + ",".join(f"j{j}:{v:.2f}x" for j, v in sp.items())
                  + f" insert_stall_reduction={stall:.2f}x")
            return
        from benchmarks.bench_search_path import run_json

        report = run_json(quick=not args.full)
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
        mult = report["probe_multiplicity"]
        saving = report["batched_traffic_saving"]
        print(f"# wrote {args.json}: probe_multiplicity={mult:.2f}x "
              f"batched_traffic_saving={saving:.2f}x")
        return

    print("name,us_per_call,derived")
    failures = 0
    for name, module in BENCHES:
        if args.only and args.only != name:
            continue
        t0 = time.time()
        try:
            import importlib

            mod = importlib.import_module(module)
            if args.dry:
                assert callable(getattr(mod, "run")), f"{module}.run missing"
                print(f"# {name} dry ok", flush=True)
                continue
            for line in mod.run(quick=not args.full):
                print(line, flush=True)
            print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# {name} FAILED:", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
