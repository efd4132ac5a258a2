"""Spans and counters of the serve path (``repro.utils.spans``): each
span's count agrees with the program's own accounting, the existing
timers read the spans, and the span names reach the profiler's trace on
the CPU."""
import os
import threading
import time

import jax
import numpy as np
import pytest

import spfresh
from bench import trace_reduce
from repro.core.types import LireConfig
from repro.serve import engine as engine_mod
from repro.serve.queue import RequestQueue, Ticket
from repro.storage.wal import iter_wal
from repro.utils.spans import span
from tests.conftest import make_clustered

DIM = 16


def _spec(root, *, async_serve=True, max_wait_ms=0.0, **dur_kw):
    cfg = LireConfig(
        dim=DIM, block_size=8, max_blocks_per_posting=8, num_blocks=1024,
        num_postings_cap=128, num_vectors_cap=4096, split_limit=48,
        merge_limit=6, reassign_range=8, reassign_budget=128,
        replica_count=2, nprobe=8,
    )
    spec = spfresh.ServiceSpec(
        index=spfresh.IndexSpec(config=cfg),
        serve=spfresh.ServeSpec(search_k=10, max_batch=32,
                                async_serve=async_serve,
                                max_wait_ms=max_wait_ms),
    )
    return spec.with_durability(str(root), checkpoint_on_close=False,
                                **dur_kw)


def _delta(c0: dict, c1: dict, key: str) -> float:
    return c1.get(key, 0) - c0.get(key, 0)


def _traffic(svc, rng, rounds=4):
    """Searches, inserts and deletes; with a pump thread from two threads
    at once, so that batches of each kind form, fence each other and
    coalesce (a cooperative engine takes one caller at a time)."""
    base_id = 5000

    def worker(t):
        r = np.random.default_rng(t)
        for i in range(rounds):
            vecs = make_clustered(r, 6, DIM, n_clusters=2)
            ids = np.arange(6, dtype=np.int32) + base_id + 100 * (4 * t + i)
            svc.engine.submit_insert(vecs, ids).result(timeout=120)
            svc.engine.submit_search(vecs[:3]).result(timeout=120)
            svc.engine.submit_delete(ids[:2]).result(timeout=120)

    if not svc.engine.is_async:
        for t in range(2):
            worker(t)
        svc.flush()
        return
    threads = [threading.Thread(target=worker, args=(t,)) for t in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(300)
        assert not th.is_alive()
    svc.flush()


@pytest.mark.parametrize("async_serve", [True, False],
                         ids=["async", "cooperative"])
def test_one_step_span_per_popped_batch(tmp_path, rng, monkeypatch,
                                        async_serve):
    step_ops = []

    class Recorded(span):
        __slots__ = ()

        def __init__(self, name, counters, **kw):
            super().__init__(name, counters, **kw)
            if name == "serve.step":
                step_ops.append(kw.get("op"))

    svc = spfresh.open(_spec(tmp_path / "svc", async_serve=async_serve),
                       vectors=make_clustered(rng, 500, DIM))
    monkeypatch.setattr(engine_mod, "span", Recorded)
    try:
        eng = svc.engine
        c0, b0 = svc.report()["counters"], eng.queue.batches
        _traffic(svc, rng)
        c1, batches = svc.report()["counters"], eng.queue.batches - b0
    finally:
        svc.close()
    assert batches > 0
    # a step is a popped batch or, with a pump thread, the idle branch's
    # readbacks, ack and maintenance slot (op "idle")
    assert _delta(c0, c1, "span_n.serve.step") == len(step_ops)
    assert sum(op != "idle" for op in step_ops) == batches
    assert ("idle" in step_ops) == async_serve
    # every search batch is one dispatch and one readback; every update
    # batch one serve.update span
    searches = _delta(c0, c1, "span_n.serve.dispatch")
    assert searches > 0
    assert _delta(c0, c1, "span_n.serve.readback") == searches
    assert _delta(c0, c1, "span_n.serve.update") == batches - searches
    assert 0 < _delta(c0, c1, "scan.pages_unique") <= _delta(
        c0, c1, "scan.pages_grid")


@pytest.mark.parametrize("group_commit", [0, 3])
def test_fsync_spans_count_the_wal_fsyncs(tmp_path, rng, monkeypatch,
                                          group_commit):
    svc = spfresh.open(_spec(tmp_path / "svc", group_commit=group_commit),
                       vectors=make_clustered(rng, 500, DIM))
    try:
        wal = svc.backend.wal_set
        fds = {log._fh.fileno() for log in wal.logs}
        synced = []
        fsync = os.fsync

        def counted_fsync(fd):
            synced.append(fd in fds)
            fsync(fd)

        def log_state():
            return (sum(os.path.getsize(log.path) for log in wal.logs),
                    sum(1 for _ in iter_wal(wal.shard_path(0))))

        c0, s0, (size0, recs0) = svc.report()["counters"], wal.stats(), \
            log_state()
        monkeypatch.setattr(os, "fsync", counted_fsync)
        _traffic(svc, rng)
        monkeypatch.setattr(os, "fsync", fsync)
        c1, s1, (size1, recs1) = svc.report()["counters"], wal.stats(), \
            log_state()
    finally:
        svc.close()
    fsyncs = _delta(c0, c1, "span_n.wal.fsync")
    assert fsyncs > 0 and fsyncs == sum(synced)
    assert s1["fsyncs"] - s0["fsyncs"] == fsyncs
    appends = _delta(c0, c1, "span_n.wal.append")
    assert appends == recs1 - recs0 == s1["appends"] - s0["appends"]
    assert _delta(c0, c1, "wal.bytes") == size1 - size0
    # only a group commit leaves fsyncs to the ack point, outside appends
    assert (_delta(c0, c1, "wal.sync_s") > 0) == (group_commit > 1)


def test_queue_wait_covers_the_formation_window(tmp_path, rng):
    window_ms = 30.0
    svc = spfresh.open(_spec(tmp_path / "svc", max_wait_ms=window_ms),
                       vectors=make_clustered(rng, 500, DIM))
    try:
        q = make_clustered(rng, 4, DIM)
        svc.engine.search(q[:1])                    # warm the bucket
        c0 = svc.report()["counters"]
        for row in q:               # one at a time: every one is held
            svc.engine.search(row[None])
        c1 = svc.report()["counters"]
    finally:
        svc.close()
    rows = _delta(c0, c1, "queue.rows.search")
    assert rows == len(q)
    assert _delta(c0, c1, "queue.wait_s.search") / rows >= window_ms / 1e3
    assert _delta(c0, c1, "span_n.queue.window") >= len(q)


def test_queue_counts_row_seconds_per_op():
    q = RequestQueue((8,))
    for n in (3, 2):
        t = Ticket("insert", n, ())
        q.submit(t, {"vids": np.arange(n, dtype=np.int32)})
    b = q.pop_batch()
    assert b.n_valid == 5 and b.seq == 1
    assert q.counters["queue.rows.insert"] == 5
    assert q.counters["queue.wait_s.insert"] >= 0.0
    assert "queue.rows.search" not in q.counters


def test_maintenance_time_is_the_maintain_span(tmp_path, rng):
    svc = spfresh.open(_spec(tmp_path / "svc"),
                       vectors=make_clustered(rng, 500, DIM))
    calls = []

    def timed(fn):
        def run(*a, **kw):
            t0 = time.perf_counter()
            out = fn(*a, **kw)
            calls.append(time.perf_counter() - t0)
            return out
        return run

    backend = svc.engine.backend
    backend.maintain = timed(backend.maintain)
    backend.drain = timed(backend.drain)
    try:
        _traffic(svc, rng, rounds=6)
        svc.engine.drain()
        rep = svc.report()
    finally:
        svc.close()
    m, c = rep["maintenance"], rep["counters"]
    # one slot per maintenance or drain dispatch, each timed by its span
    assert m["slots"] == len(calls) > 1
    assert m["time_s"] == c["span_s.serve.maintain"] >= sum(calls)
    assert 0.0 <= rep["insert_stall_s"] <= c["span_s.serve.maintain"]


def test_span_counts_even_when_the_block_raises():
    counters = {}
    with pytest.raises(ValueError):
        with span("x", counters):
            raise ValueError
    assert counters["span_n.x"] == 1 and counters["span_s.x"] >= 0.0


def test_span_names_reach_the_trace(tmp_path, rng):
    svc = spfresh.open(_spec(tmp_path / "svc"),
                       vectors=make_clustered(rng, 500, DIM))
    try:
        q = make_clustered(rng, 4, DIM)
        svc.engine.search(q)                       # compile outside
        trace_dir = str(tmp_path / "trace")
        jax.profiler.start_trace(trace_dir)
        try:
            for row in q:
                svc.engine.search(row[None])
            ids = np.arange(9000, 9004, dtype=np.int32)
            svc.engine.insert(q, ids)
            svc.engine.delete(ids)
        finally:
            jax.profiler.stop_trace()
    finally:
        svc.close()
    events = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    names = {e[2] for e in events if e[0].startswith("/host:")}
    # (an fsync can be shorter than the trace's 5 us floor for host events)
    assert {"serve.step", "serve.dispatch", "serve.readback",
            "serve.update", "wal.append"} <= names, names
