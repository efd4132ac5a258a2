"""Device numbers over several chips, on hand-made events of four device
planes whose answers are known (a CPU trace has no device plane): busy
and kernel time are averaged over the chips, the roofline's peaks are
the chips' together, and one plane gives exactly the one-chip numbers.
Then the readers of the program's window counters, on hand-made
readings and in a traced run at a size the CPU holds."""
from __future__ import annotations

import time

import numpy as np
import pytest

import bench.peaks
from bench import harness, registry, trace_reduce as tr
from bench.metrics import _common
from bench_tiny import fresh_batch_buffers, tiny_cell

HOST = ("/host:CPU", "spfresh-pump")


def _dev(chip, name, start, dur):
    return (f"/device:TPU:{chip}", tr.OP_LINE, name, start, dur)


def _four_planes():
    """The slice [0, 100); chip c runs a scan of 10 + 10·c and one other
    op of 20, so it is busy 30 + 10·c."""
    ev = [(*HOST, tr.SLICE_SPAN, 0, 100), (*HOST, "serve.step", 0, 100)]
    for c in range(4):
        ev += [_dev(c, "scan_batched_topk.1", 5, 10 + 10 * c),
               _dev(c, "fusion.5", 60, 20)]
    return ev, tr.slice_bounds(ev)


def test_busy_and_kernel_time_average_over_chips():
    ev, (lo, hi) = _four_planes()
    # busy 30, 40, 50, 60
    assert tr.busy_s(ev, lo, hi, 4) == pytest.approx(45e-9)
    kernel, n = tr.kernel_s(ev, "scan_batched_topk", 4)
    assert n == 4 and kernel == pytest.approx(25e-9)
    # the default reads the first chip alone
    assert tr.busy_s(ev, lo, hi) == pytest.approx(30e-9)
    assert tr.kernel_s(ev, "scan_batched_topk") == (pytest.approx(10e-9), 1)
    share = _common.device_idle_share({"trace_events": ev,
                                       "trace_bounds": (lo, hi), "chips": 4})
    assert share == pytest.approx(55.0)


def test_one_plane_reads_as_one_chip():
    """With a single device plane, asking for four chips changes
    nothing, and the breakdown is the one-chip one, key for key."""
    ev = [e for e in _four_planes()[0] if not e[0].endswith(("1", "2", "3"))]
    lo, hi = tr.slice_bounds(ev)
    assert tr.busy_s(ev, lo, hi, 4) == tr.busy_s(ev, lo, hi)
    assert tr.kernel_s(ev, "scan", 4) == tr.kernel_s(ev, "scan")
    assert tr.breakdown(ev, lo, hi, chips=4) == tr.breakdown(ev, lo, hi)
    assert "gaps_chip" not in tr.breakdown(ev, lo, hi)


def test_breakdown_over_chips():
    ev, (lo, hi) = _four_planes()
    bd = tr.breakdown(ev, lo, hi, chips=4)
    assert bd["gaps_chip"] == "/device:TPU:0"
    assert dict(bd["device_ops"]) == pytest.approx(
        {"scan_batched_topk.1": 25e-9, "fusion.5": 20e-9})
    # chip 0's gaps: [15, 60) and [80, 100) and [0, 5)
    assert [g[1] for g in bd["idle_gaps"]] == pytest.approx(
        [45e-9, 20e-9, 5e-9])
    assert all(g[0] == "serve.step" for g in bd["idle_gaps"])


def test_scan_roofline_over_chips():
    ev, _ = _four_planes()
    v5e = bench.peaks.peaks("TPU v5 lite")
    roof = {"events": ev, "bytes": 4 * 819.0, "flops": 1.0}
    one = _common.scan_roofline({"roof": roof, "device_kind": "TPU v5 lite"})
    four = _common.scan_roofline({"roof": roof, "device_kind": "TPU v5 lite",
                                  "chips": 4})
    # one chip: 3,276 bytes at 819 GB/s = 4 ns in chip 0's 10 ns
    assert one == pytest.approx(100 * 4 * 819 / v5e["hbm_bytes_per_s"]
                                / 10e-9)
    # four chips: 1 ns at four chips' bandwidth in the mean kernel's 25 ns
    assert four == pytest.approx(100 * 819 / v5e["hbm_bytes_per_s"] / 25e-9)


def _readings(c0: dict, c1: dict) -> dict:
    win = harness.Window(start=0.0, seconds=1.0, op=np.zeros(0, np.int8),
                         t_sched=np.zeros(0), payload=np.zeros(0, np.int64),
                         tickets=[], t_submit=np.zeros(0), counters0=c0,
                         counters1=c1, answered=np.zeros(0, bool),
                         refused=np.zeros(0, bool))
    return harness.readings(None, win, setup_s=1.0, recall=1.0, roof=None,
                            device_kind="TPU v5 lite", chips=4)


def test_delta_covers_counters_first_counted_in_the_window():
    r = _readings({"backlog": 3, "queue.rows.search": 10},
                  {"backlog": 5, "queue.rows.search": 14,
                   "queue.wait_s.search": 0.02, "scan.pages_unique": 30,
                   "scan.pages_grid": 120})
    assert r["chips"] == 4
    assert r["delta"] == pytest.approx({
        "backlog": 2, "queue.rows.search": 4, "queue.wait_s.search": 0.02,
        "scan.pages_unique": 30, "scan.pages_grid": 120})
    read = registry.metric_reader
    assert read("queue_wait_ms")(r) == pytest.approx(5.0)
    assert read("scan_page_use")(r) == pytest.approx(25.0)


def test_counter_readers_read_nothing_without_counts():
    r = _readings({"backlog": 0}, {"backlog": 0})
    assert registry.metric_reader("queue_wait_ms")(r) is None
    assert registry.metric_reader("scan_page_use")(r) is None


def test_chips_must_match_the_mesh(tmp_path):
    """A cell whose chips differ from its configuration's shards is
    refused before anything is built."""
    cell = "spfresh1b-spacev-shard.churn"
    config, traffic = tiny_cell(cell)
    config["shards"] = {"n_shards": 4}
    with pytest.raises(ValueError, match="n_shards x n_replicas = 4"):
        harness.run_cell(cell, 1, 1.0, False, t_start=time.perf_counter(),
                         config=config, traffic=traffic, require_tpu=False,
                         workdir=tmp_path / "root")
    assert not (tmp_path / "root").exists()


def test_traced_run_reads_the_window_counters(monkeypatch, tmp_path):
    """A traced run on the CPU: the queue's wait and the scan's page use
    read from the program's own counters over the window."""
    cell = "spfresh1b-spacev-shard.churn"
    fresh_batch_buffers(monkeypatch)
    v5e = bench.peaks.peaks("TPU v5 lite")
    monkeypatch.setattr(bench.peaks, "peaks", lambda kind: v5e)
    config, traffic = tiny_cell(cell)
    out = harness.run_cell(cell, 2**31 + 41, 2.0, True,
                           t_start=time.perf_counter(), config=config,
                           traffic=traffic, require_tpu=False,
                           workdir=tmp_path / "root")
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert m["queue_wait_ms"]["value"] > 0, m
    assert 0 < m["scan_page_use"]["value"] <= 100, m
    assert out["device"]["memory_peak_bytes_per_device"] == [
        out["device"]["memory_peak_bytes"]]
