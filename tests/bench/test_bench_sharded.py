"""A 4-shard configuration through ``harness.run_cell`` on four CPU
devices (``sharded_script.py``, in a process of its own): the sound run
is correct under the one-chip cell's checks and limits, reports every
device, and each fault of the harness's map between rows and the
backend's handles comes out not correct."""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import pytest

SCRIPT = pathlib.Path(__file__).with_name("sharded_script.py")


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, str(SCRIPT), "--cpu"],
                       capture_output=True, text=True, timeout=120, env=env)
    assert p.returncode == 0, p.stderr[-4000:]
    out = {}
    for line in p.stdout.splitlines():
        row = json.loads(line)
        out[row["case"]] = row
    return out


def test_sound_sharded_run_is_correct(runs):
    r = runs["sound"]
    assert r["correct"], r["checks"]
    assert r["failed"] == 0
    assert r["checks"]["insert_missing"] == 0
    assert r["checks"]["deleted_returned"] == 0
    assert set(r["checks"]) >= {"recall_gap", "post_recall_gap", "dist_err",
                                "insert_refused", "unanswered"}
    dev = r["device"]
    assert dev["count"] == 4
    assert len(dev["memory_peak_bytes_per_device"]) == 4
    assert dev["memory_peak_bytes"] == max(dev["memory_peak_bytes_per_device"])


@pytest.mark.parametrize("fault", ["handle_shifted", "raw_handles"])
def test_handle_fault_is_not_correct(runs, fault):
    r = runs[fault]
    assert not r["correct"], r["checks"]
    assert r["checks"]["dist_err"] > 0, r["checks"]
