"""The per-layer metrics that read the program's own spans from a traced
slice: their arithmetic on hand-made events, no reading (and no error)
where the program writes no spans, as the TPU trace recorded before the
spans existed shows, and a whole traced run at a size the CPU holds."""
from __future__ import annotations

import gzip
import json
import pathlib
import time

import pytest

import bench.peaks
from bench import harness, registry, trace_reduce as tr
from bench.metrics import _common, _spans
from bench_tiny import fresh_batch_buffers, tiny_cell

SPAN_METRICS = ["pump_busy_share", "wal_busy_share",
                "host_bound_idle_share.churn"]
FIXTURE = pathlib.Path(__file__).with_name("trace_roof_v5e.json.gz")
DEV = ("/device:TPU:0", tr.OP_LINE)
HOST = ("/host:CPU", "spfresh-pump")


def _dev(name, start, dur):
    return (*DEV, name, start, dur)


def _host(name, start, dur):
    return (*HOST, name, start, dur)


def _pump_trace():
    """The device runs [10,30) [50,60) [80,90) of the slice [0,100), so
    it idles 60; the pump's spans tile the slice."""
    ev = [_dev("a", 10, 20), _dev("b", 50, 10), _dev("c", 80, 10),
          _host(tr.SLICE_SPAN, 0, 100),
          _host("serve.idle", -20, 32),          # opened before the slice
          _host("serve.step", 12, 33), _host("serve.readback", 30, 12),
          _host("wal.append", 20, 6), _host("wal.fsync", 22, 3),
          _host("queue.window", 45, 10),
          _host("serve.step", 55, 30), _host("wal.fsync", 76, 4),
          _host("serve.idle", 85, 15)]
    return ev, tr.slice_bounds(ev)


def test_idle_splits_by_pump_span():
    """Idle time under each pump span adds up to the device's idle share,
    and every idle gap is named by the pump span open across it."""
    ev, bounds = _pump_trace()
    r = {"trace_events": ev, "trace_bounds": bounds}
    # under serve.step: [30,45) + [60,80) = 35 of 100
    assert _spans.host_bound_idle_share(r) == pytest.approx(35.0)
    split = _spans.idle_split(ev, bounds)
    assert split == pytest.approx({"device_idle": 60.0, "serve.step": 35.0,
                                   "serve.idle": 20.0, "queue.window": 5.0,
                                   "outside": 0.0})
    assert sum(split[n] for n in _spans.PUMP_SPANS) == \
        pytest.approx(_common.device_idle_share(r))
    gaps = tr.breakdown(ev, *bounds)["idle_gaps"]
    assert [g[0] for g in gaps] == ["serve.readback", "serve.step",
                                    "serve.idle", "serve.idle"]


def test_span_shares_read_the_union_inside_the_slice():
    ev, bounds = _pump_trace()
    r = {"trace_events": ev, "trace_bounds": bounds}
    read = registry.metric_reader
    # serve.step [12,45) + [55,85) = 63 of 100
    assert read("pump_busy_share")(r) == pytest.approx(63.0)
    # wal.append [20,26) holds its fsync [22,25); one fsync [76,80) apart
    assert read("wal_busy_share")(r) == pytest.approx(10.0)
    # a slice without a WAL span reads 0, not nothing
    quiet = [e for e in ev if not e[2].startswith("wal.")]
    assert read("wal_busy_share")(
        {"trace_events": quiet, "trace_bounds": bounds}) == 0.0


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(FIXTURE) as f:
        return [tuple(e) for e in json.load(f)]


@pytest.mark.parametrize("trace", ["untraced", "no_pump_span", "recorded"])
@pytest.mark.parametrize("name", SPAN_METRICS)
def test_no_pump_span_no_reading(name, trace, recorded):
    """A run without a trace, or of a program that writes no spans (the
    TPU trace recorded before they existed), reads nothing and raises
    nothing."""
    if trace == "untraced":
        r = {"trace_events": None, "trace_bounds": None}
    else:
        ev = recorded if trace == "recorded" else [
            e for e in _pump_trace()[0]
            if not e[2].startswith(("serve.", "queue.", "wal."))]
        r = {"trace_events": ev, "trace_bounds": tr.slice_bounds(ev)}
    assert registry.metric_reader(name)(r) is None


def test_traced_run_reads_the_span_metrics(monkeypatch, tmp_path):
    """A traced run on the CPU: the span shares read, the device's idle
    share under the pump has no device to read (the v5e's peaks stand in
    for the CPU's, which the table rightly lacks)."""
    cell = "spfresh1b-spacev-shard.churn"
    fresh_batch_buffers(monkeypatch)
    v5e = bench.peaks.peaks("TPU v5 lite")
    monkeypatch.setattr(bench.peaks, "peaks", lambda kind: v5e)
    config, traffic = tiny_cell(cell)
    out = harness.run_cell(cell, 2**31 + 99, 2.0, True,
                           t_start=time.perf_counter(), config=config,
                           traffic=traffic, require_tpu=False,
                           workdir=tmp_path / "root")
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert 0 < m["pump_busy_share"]["value"] <= 100, m
    assert 0 <= m["wal_busy_share"]["value"] <= 100, m
    assert "host_bound_idle_share.churn" not in m       # no device plane
