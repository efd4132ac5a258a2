"""A whole run at a size the CPU holds, without the chip: the sound
program comes out correct, the controls and every fault a one-chip cell
can have come out not correct.

Faults are planted underneath the timed path, in the serving backend the
window drives: an update step that leaves the state unchanged, an insert
step that refuses every row, half of each search batch left out, and an
answer altered where it is produced.
(The exchange between chips does not exist on one chip.)
"""
from __future__ import annotations

import time

import numpy as np
import pytest

from bench import harness
from bench_tiny import fresh_batch_buffers, tiny_cell
from repro.serve.engine import LocalBackend

CELL = "spfresh1b-spacev-shard.churn"
SEED = 2**31 + 99


def _run(monkeypatch, workdir, control=False):
    fresh_batch_buffers(monkeypatch)
    config, traffic = tiny_cell(CELL)
    return harness.run_cell(CELL, SEED, 2.0, False,
                            t_start=time.perf_counter(), config=config,
                            traffic=traffic, require_tpu=False,
                            control=control, workdir=workdir)


def _failed(checks: dict) -> list[str]:
    config, _ = tiny_cell(CELL)
    return [k for k, v in checks.items()
            if v is not None and k in config["limits"]
            and v > config["limits"][k]]


def test_sound_run_is_correct_and_controls_are_not(monkeypatch, tmp_path):
    out = _run(monkeypatch, tmp_path / "root", control=True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 20 * 4
    assert list(out)[-1] == "checks"
    assert "recall_gap" in _failed(out["control_int4"]), out["control_int4"]
    assert "insert_missing" in _failed(out["control_stale"]), \
        out["control_stale"]
    refuse = _failed(out["control_refuse"])
    assert {"insert_refused", "insert_missing"} <= set(refuse), refuse
    assert out["checks"]["insert_refused"]["value"] == 0.0
    assert set(out["host"]) >= {"stall_max_ms", "gc_max_ms"}
    assert out["lateness_ms"]["max"] >= out["lateness_ms"]["median"] >= 0


def _wrap_search(monkeypatch, alter):
    orig = LocalBackend.search_begin

    def search_begin(self, queries, k, nprobe, valid=None):
        fin = orig(self, queries, k, nprobe, valid)

        def finalize():
            d, v = fin()
            return alter(np.array(d), np.array(v), valid)
        return finalize
    monkeypatch.setattr(LocalBackend, "search_begin", search_begin)


def _half_left_out(d, v, valid):
    rows = np.nonzero(valid)[0] if valid is not None else np.arange(len(v))
    drop = rows[len(rows) // 2:] if len(rows) > 1 else rows
    v[drop] = -1
    d[drop] = np.float32(3e38)
    return d, v


def _answer_altered(d, v, valid):
    v[:, 0] = np.where(v[:, 0] >= 0, v[:, 0] + 1, v[:, 0])
    return d, v


def _state_unchanged(monkeypatch):
    def insert(self, vecs, vids, valid):
        return np.asarray(vids), np.asarray(valid, bool).copy()

    monkeypatch.setattr(LocalBackend, "insert", insert)
    monkeypatch.setattr(LocalBackend, "delete", lambda self, vids, valid: None)


def _inserts_refused(monkeypatch):
    def insert(self, vecs, vids, valid):
        return np.asarray(vids), np.zeros(len(vids), bool)

    monkeypatch.setattr(LocalBackend, "insert", insert)


@pytest.mark.parametrize("fault,caught_by", [
    ("state_unchanged", "insert_missing"),
    ("inserts_refused", "insert_refused"),
    ("half_batch", "recall_gap"),
    ("answer_altered", "dist_err"),
])
def test_fault_is_not_correct(monkeypatch, tmp_path, fault, caught_by):
    if fault == "state_unchanged":
        _state_unchanged(monkeypatch)
    elif fault == "inserts_refused":
        _inserts_refused(monkeypatch)
    elif fault == "half_batch":
        _wrap_search(monkeypatch, _half_left_out)
    else:
        _wrap_search(monkeypatch, _answer_altered)
    out = _run(monkeypatch, tmp_path / "root")
    assert not out["correct"], out["checks"]
    assert caught_by in _failed({k: c["value"] for k, c in
                                 out["checks"].items()}), out["checks"]
