"""Finds a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one
metric lives in a file of its own; ``BENCHMARK.json`` names them:

* ``configs[].file``            — the configuration (JSON);
* ``bench/traffic/<traffic>.json`` — the traffic mix (JSON);
* ``bench/metrics/<metric>.py``  — a reader ``read(r) -> float | None``
  over the readings of one run (see ``harness.Readings``).

A new cell is a new file plus a ``BENCHMARK.json`` entry; nothing here
changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH_DIR = ROOT / "bench"


def load(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            with open(root / c["file"]) as f:
                return json.load(f)
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, root: pathlib.Path = ROOT) -> dict:
    with open(root / "bench" / "traffic" / f"{name}.json") as f:
        return json.load(f)


def metric_reader(name: str, root: pathlib.Path = ROOT):
    """The ``read`` function of ``bench/metrics/<name>.py`` (a name may
    hold dots, so the file is loaded by path, not imported by name)."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path
    )
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics with
    ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]
