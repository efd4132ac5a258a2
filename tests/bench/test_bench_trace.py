"""The reduction from a profiler trace to device numbers, on a small
trace recorded on a TPU v5e (eight 128-query search dispatches of the
SPACEV shard after a churn window, flattened by ``load_xplane``) and on
hand-made events whose answers are known."""
from __future__ import annotations

import gzip
import json
import pathlib

import pytest

from bench import trace_reduce as tr
from bench.metrics import _common

FIXTURE = pathlib.Path(__file__).with_name("trace_roof_v5e.json.gz")


@pytest.fixture(scope="module")
def events():
    with gzip.open(FIXTURE) as f:
        return [tuple(e) for e in json.load(f)]


def test_recorded_trace(events):
    lo, hi = tr.slice_bounds(events)
    assert (hi - lo) / 1e9 == pytest.approx(0.393798816)
    assert tr.busy_s(events, lo, hi) == pytest.approx(0.362136108)
    kernel, n = tr.kernel_s(events, _common.SCAN_KERNEL)
    assert n == 8 and kernel == pytest.approx(0.144628817)
    bd = tr.breakdown(events, lo, hi)
    assert bd["device_ops"][0][0] == "scan_batched_topk.1"
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10
    assert all(g[1] > 0 for g in bd["idle_gaps"][:9])


def test_scan_roofline_from_recorded_trace(events):
    roof = {"events": events, "bytes": 8 * 56e6, "flops": 8 * 1e9}
    share = _common.scan_roofline({"roof": roof,
                                   "device_kind": "TPU v5 lite"})
    # bytes bound: 448 MB / 819 GB/s over 0.1446 s of kernel time
    assert share == pytest.approx(100 * 448e6 / 819e9 / 0.144628817)
    assert _common.scan_roofline({"roof": None,
                                  "device_kind": "TPU v5 lite"}) is None
    with pytest.raises(KeyError):
        _common.scan_roofline({"roof": roof, "device_kind": "TPU v9"})


DEV = "/device:TPU:0"


def _ev(name, start, dur, plane=DEV, line=tr.OP_LINE):
    return (plane, line, name, start, dur)


def test_union_clips_and_merges():
    ev = [_ev("a", 0, 10), _ev("b", 5, 10), _ev("c", 30, 10),
          _ev("d", 100, 50), ("/host:CPU", "t", tr.SLICE_SPAN, 8, 124),
          ("/host:CPU", "t", "host.wait", 16, 14)]
    lo, hi = tr.slice_bounds(ev)
    assert (lo, hi) == (8, 132)
    # [8,15) + [30,40) + [100,132) = 7 + 10 + 32
    assert tr.busy_s(ev, lo, hi) == pytest.approx(49e-9)
    share = _common.device_idle_share({"trace_events": ev,
                                       "trace_bounds": (lo, hi)})
    assert share == pytest.approx(100 * (1 - 49 / 124))
    bd = tr.breakdown(ev, lo, hi)
    # gaps: [15,30) named by the host event covering 22, [40,100) by none
    assert bd["idle_gaps"] == [["no host event", 60e-9],
                               ["host.wait", 15e-9]]


def test_op_name_is_the_instruction_name():
    full = "%scan_batched_topk.1 = (f32[8]) custom-call(s32[8] %x)"
    assert tr.op_name(full) == "scan_batched_topk.1"
    assert tr.op_name("fusion.5") == "fusion.5"
