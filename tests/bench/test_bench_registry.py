"""BENCHMARK.json against the benchmark's contract, and every cell's files
found by name."""
from __future__ import annotations

import json
import re
import shutil

import pytest

from bench import registry

BENCH = registry.load()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all((registry.ROOT / p).is_dir() for p in BENCH["paths"])
    assert all(not w.startswith("/") and ".." not in w
               for w in BENCH["command"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    w = registry.workload(BENCH, cell)
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    config = registry.config(BENCH, w["config"])
    assert config["name"] == w["config"]
    traffic = registry.traffic(w["traffic"])
    assert traffic["rate_per_s"] > 0
    for trace in (False, True):
        metrics = registry.cell_metrics(BENCH, cell, trace)
        assert metrics, (cell, trace)
        for m in metrics:
            assert callable(registry.metric_reader(m["name"]))
    e2e = {m["name"] for m in registry.cell_metrics(BENCH, cell, False)}
    assert "setup_s" in e2e and len(e2e) >= 2


@pytest.mark.parametrize("group", ["end_to_end", "per_layer"])
def test_metric_entries(group):
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH[group]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        for c in m.get("workloads", []):
            assert c in CELLS
        if group == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
        else:
            assert m["moves"] in e2e and "\n" not in m["layer"]


@pytest.mark.parametrize("cell", CELLS)
def test_per_layer_moves_what_the_cell_reports(cell):
    """Each per-layer metric of a cell moves an end-to-end metric that the
    cell reports."""
    e2e = {m["name"] for m in registry.cell_metrics(BENCH, cell, False)}
    for m in registry.cell_metrics(BENCH, cell, True):
        assert m["moves"] in e2e, (cell, m["name"], m["moves"])


def test_names_and_configs():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names)
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
        cfg = registry.config(BENCH, c["name"])
        assert set(c["reduced"]) <= set(cfg["data"]) | set(cfg["index"])
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_new_cell_is_files_plus_an_entry(tmp_path):
    """A configuration, a traffic mix and a metric are added as new files
    and BENCHMARK.json entries; the registry finds them with no edit to
    any file that was there."""
    root = tmp_path / "repo"
    shutil.copytree(registry.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    src = registry.config(bench, "spfresh1b-spacev-shard")
    new_cfg = dict(src, name="new-shard")
    (root / "bench/configs/new-shard.json").write_text(json.dumps(new_cfg))
    (root / "bench/traffic/new-mix.json").write_text(json.dumps(
        dict(registry.traffic("spacev-churn"), rate_per_s=7.0)))
    (root / "bench/metrics/new_metric.py").write_text(
        "def read(r):\n    return 2.0 * r['window_s']\n")
    bench["configs"].append({"name": "new-shard", "source": "x",
                             "file": "bench/configs/new-shard.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new-shard.mix", "config": "new-shard",
                               "traffic": "new-mix", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new_metric", "unit": "s",
                               "better": "lower", "source": "host_clock",
                               "layer": "device", "moves": "setup_s",
                               "workloads": ["new-shard.mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    loaded = registry.load(root)
    assert registry.config(loaded, "new-shard", root)["name"] == "new-shard"
    assert registry.traffic("new-mix", root)["rate_per_s"] == 7.0
    names = [m["name"] for m in registry.cell_metrics(loaded, "new-shard.mix",
                                                      True)]
    assert names == ["new_metric"]
    assert registry.metric_reader("new_metric", root)({"window_s": 3}) == 6.0
