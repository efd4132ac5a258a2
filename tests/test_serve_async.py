"""Async serving gate: background pump thread correctness.

The stress test drives an async engine from N submitter threads with a
mixed search/insert/delete stream and asserts the three things the pump
thread must never break: result integrity (every search's top-1 is the
exact vector the same thread inserted and awaited earlier), live-set
conservation (inserted − deleted rows all survive, none resurrect), and
no deadlock (join timeouts + a faulthandler watchdog instead of
pytest-timeout, which this environment does not ship).

The rest are satellite regressions: the batch-formation window, the
falsy-zero ``submit_search`` key fix, the bounded latency reservoir,
and ``ticket.dropped`` backpressure accounting.
"""
import faulthandler
import logging
import threading
import time

import numpy as np
import pytest

# check.sh runs this suite as its own explicit gate step; the tier-1
# step excludes it via the marker (no hand-maintained --ignore list).
pytestmark = pytest.mark.gate

from repro.core.index import SPFreshIndex
from repro.serve.engine import (
    EngineConfig,
    ServeEngine,
    ServeMetrics,
    _LatReservoir,
)
from repro.serve.queue import (
    INSERT,
    SEARCH,
    RequestQueue,
    Ticket,
    default_buckets,
)
from tests.conftest import make_clustered
from tests.test_lire import small_cfg

DIM = 16


def _async_engine(rng, n_base=600, **cfg_kw):
    base = make_clustered(rng, n_base, DIM, n_clusters=4)
    idx = SPFreshIndex.build(small_cfg(), base)
    cfg = dict(
        search_k=10, max_batch=32, min_bucket=8,
        policy="ratio", fg_bg_ratio=2, maintain_budget=4,
        async_serve=True,
        # the whole async suite runs under the instrumented lock: any
        # shared-field write off the declared ownership map raises
        lock_check=True,
    )
    cfg.update(cfg_kw)
    return ServeEngine(idx, EngineConfig(**cfg)), base


# ---------------------------------------------------------------------------
# Pump thread lifecycle
# ---------------------------------------------------------------------------

def test_async_engine_roundtrip_and_shutdown(rng):
    eng, base = _async_engine(rng)
    assert eng.is_async and eng.report()["async"]
    d, v = eng.search(base[:4], k=5)
    assert v.shape == (4, 5) and (v[:, 0] == np.arange(4)).all()

    vecs = make_clustered(rng, 8, DIM)
    ids = np.arange(5000, 5008, dtype=np.int32)
    tk = eng.submit_insert(vecs, ids)
    got_ids, landed = tk.result(timeout=60)
    assert landed.all() and (got_ids == ids).all()
    _, hit = eng.search(vecs, k=3)
    assert (hit[:, 0] == ids).all()

    eng.shutdown()
    assert not eng.is_async
    # post-shutdown the engine reverts to cooperative pumping
    _, hit = eng.search(vecs[:2], k=1)
    assert (hit[:, 0] == ids[:2]).all()


def test_async_pump_error_surfaces_at_result(rng):
    eng, _ = _async_engine(rng)
    try:
        # sabotage the backend: the pump thread hits this on dispatch
        def boom(*a, **k):
            raise RuntimeError("injected backend failure")

        eng.backend.insert = boom
        tk = eng.submit_insert(
            make_clustered(rng, 4, DIM), np.arange(4, dtype=np.int32)
        )
        with pytest.raises(RuntimeError, match="pump thread died"):
            tk.result(timeout=60)
    finally:
        # deliberate internals poke (clearing a simulated pump error from
        # the main thread): bypass the ownership checker explicitly
        object.__setattr__(eng, "_pump_error", None)
        eng.shutdown()


def test_dispatch_arguments_own_their_memory():
    """The queue refills a staging buffer as soon as its dispatch returns,
    so the padded entry points' device arguments must not alias it (on
    the CPU ``jnp.asarray`` would, under an asynchronous dispatch)."""
    from repro.core.index import _owned

    buf = np.zeros((32, 8), np.float32)
    arg = _owned(buf)
    buf[:] = 1.0
    assert float(np.asarray(arg).sum()) == 0.0


# ---------------------------------------------------------------------------
# Multi-threaded stress: integrity, conservation, no deadlock
# ---------------------------------------------------------------------------

def test_async_multithreaded_stress(rng):
    n_threads, ops_each = 4, 60
    eng, base = _async_engine(rng, n_base=800, max_wait_ms=1.0)
    st0 = eng.stats()
    faulthandler.dump_traceback_later(240, exit=False)
    errors: list[BaseException] = []
    live_sets: list[dict[int, np.ndarray]] = [{} for _ in range(n_threads)]
    dead_sets: list[dict[int, np.ndarray]] = [{} for _ in range(n_threads)]
    op_counts = [0] * n_threads

    def worker(tid: int) -> None:
        trng = np.random.default_rng(100 + tid)
        # vids must stay < num_vectors_cap (8192): the version map is
        # sized by it, and over-cap vids are GC'd at the next split
        vid = 2000 + 1000 * tid
        live, dead = live_sets[tid], dead_sets[tid]
        try:
            for i in range(ops_each):
                op = trng.integers(0, 10)
                if op < 5 or not live:            # insert
                    v = make_clustered(trng, 1, DIM)
                    ids = np.asarray([vid], np.int32)
                    got, landed = eng.submit_insert(v, ids).result(
                        timeout=120)
                    assert landed.all(), f"t{tid} op{i}: insert rejected"
                    live[vid] = v
                    vid += 1
                elif op < 8:                      # search for an OWN vector
                    pick = int(trng.choice(sorted(live)))
                    # integrity = ORDERING, not ANN recall: the awaited
                    # insert must be visible to a later search dispatch.
                    # Probe wide (nprobe=32 vs config 8) so replica
                    # placement under concurrent splits can't alias a
                    # pipeline reordering bug as a recall miss.
                    d, hit = eng.submit_search(
                        live[pick], k=5, nprobe=32).result(timeout=120)
                    assert pick in hit[0].tolist(), (
                        f"t{tid} op{i}: vid {pick} invisible: {hit[0]}"
                    )
                else:                             # delete an OWN vector
                    pick = int(trng.choice(sorted(live)))
                    eng.submit_delete(
                        np.asarray([pick], np.int32)).result(timeout=120)
                    dead[pick] = live.pop(pick)
                op_counts[tid] += 1
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [
        threading.Thread(target=worker, args=(t,), daemon=True)
        for t in range(n_threads)
    ]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        hung = [t.name for t in threads if t.is_alive()]
        assert not hung, f"deadlock: submitters still alive: {hung}"
        if errors:
            raise errors[0]
        assert sum(op_counts) == n_threads * ops_each
        eng.pump()                                # flush barrier
        assert eng._pump_error is None

        # live-set conservation, counter side: every landed insert and
        # every delete reached the state exactly once
        st = eng.stats()
        n_ins = sum(len(l) for l in live_sets) + sum(
            len(d) for d in dead_sets)
        n_del = sum(len(d) for d in dead_sets)
        assert st["n_inserts"] - st0["n_inserts"] == n_ins
        assert st["n_deletes"] - st0["n_deletes"] == n_del
        assert eng.report()["insert_dropped"] == 0

        # ...and recall side: survivors stay findable, tombstones stay gone
        for live, dead in zip(live_sets, dead_sets):
            for pick in sorted(live)[:3]:
                _, hit = eng.search(live[pick], k=5, nprobe=32)
                assert pick in hit[0].tolist(), "live vector lost"
            for pick in sorted(dead)[:3]:
                _, hit = eng.search(dead[pick], k=5, nprobe=32)
                assert pick not in hit[0].tolist(), "delete resurrected"
    finally:
        faulthandler.cancel_dump_traceback_later()
        eng.shutdown()


# ---------------------------------------------------------------------------
# Batch-formation window (queue-level)
# ---------------------------------------------------------------------------

def test_window_coalesces_head_run():
    q = RequestQueue(default_buckets(8, 8), max_wait_ms=500.0)
    t1 = Ticket(SEARCH, 4, (10, None))
    q.submit(t1, {"queries": np.zeros((4, DIM), np.float32)})

    def late_submit():
        time.sleep(0.05)
        t2 = Ticket(SEARCH, 4, (10, None))
        q.submit(t2, {"queries": np.ones((4, DIM), np.float32)})

    threading.Thread(target=late_submit, daemon=True).start()
    t0 = time.perf_counter()
    b = q.pop_batch()
    took = time.perf_counter() - t0
    # the window held the 4-row head run until the second part arrived,
    # filled the top bucket, and released ONE coalesced batch (not two
    # dispatches) well before the 500ms window expired
    assert b.n_valid == 8 and b.bucket == 8
    assert took < 0.4, "window did not release on coalesced fill"
    assert q.accounting()["window_waits"] >= 1
    assert q.pop_batch() is None


def test_window_fenced_by_other_op_releases_immediately():
    q = RequestQueue(default_buckets(8, 64), max_wait_ms=500.0)
    q.submit(Ticket(SEARCH, 4, (10, None)),
             {"queries": np.zeros((4, DIM), np.float32)})
    q.submit(Ticket(INSERT, 4, ()),
             {"vecs": np.zeros((4, DIM), np.float32),
              "vids": np.arange(4, dtype=np.int32)})
    t0 = time.perf_counter()
    b = q.pop_batch()
    # a different-kind part fences the head run: no window hold
    assert b.op == SEARCH and time.perf_counter() - t0 < 0.25
    assert q.pop_batch().op == INSERT


def test_window_force_pop_skips_wait():
    q = RequestQueue(default_buckets(8, 64), max_wait_ms=500.0)
    q.submit(Ticket(SEARCH, 2, (10, None)),
             {"queries": np.zeros((2, DIM), np.float32)})
    t0 = time.perf_counter()
    b = q.pop_batch(force=True)
    assert b.n_valid == 2 and time.perf_counter() - t0 < 0.25


def test_window_expires_and_releases_partial_batch():
    q = RequestQueue(default_buckets(8, 64), max_wait_ms=40.0)
    q.submit(Ticket(SEARCH, 2, (10, None)),
             {"queries": np.zeros((2, DIM), np.float32)})
    t0 = time.perf_counter()
    b = q.pop_batch()
    took = time.perf_counter() - t0
    assert b.n_valid == 2
    assert took >= 0.02, "window never held the under-filled head run"


# ---------------------------------------------------------------------------
# Satellite regressions
# ---------------------------------------------------------------------------

def test_submit_search_explicit_zero_k_nprobe_not_replaced(rng):
    """Falsy-zero fix: k=0 / nprobe=0 must not silently become the
    config defaults (the old code used ``k or cfg.search_k``)."""
    base = make_clustered(rng, 400, DIM)
    eng = ServeEngine(SPFreshIndex.build(small_cfg(), base),
                      EngineConfig(search_k=10, nprobe=8))
    empty = np.zeros((0, DIM), np.float32)
    t = eng.submit_search(empty, k=0, nprobe=0)
    assert t.key == (0, 0), f"explicit zeros replaced by defaults: {t.key}"
    d, v = t.result()
    assert d.shape == (0, 0) and v.shape == (0, 0)
    # defaults still apply when the caller passes nothing
    assert eng.submit_search(empty).key == (10, 8)


def test_latency_reservoir_is_bounded_and_counts_all():
    r = _LatReservoir(cap=64, seed=0)
    for i in range(10_000):
        r.add(float(i))
    assert len(r.values()) == 64          # memory stays O(cap)
    assert r.n == 10_000                  # ...but the count is exact
    # algorithm R keeps a uniform sample: the mean of a 0..9999 ramp
    # must land near the middle, not stick to the first 64 values
    assert 2000 < float(np.mean(r.values())) < 8000

    m = ServeMetrics(reservoir=32)
    for i in range(500):
        tk = Ticket(SEARCH, 1, ())
        tk.t_done = tk.t_submit + 0.001 * (i + 1)
        m.note_ticket(tk)
    p = m.percentiles(SEARCH)
    assert set(p) == {"p50_ms", "p90_ms", "p99_ms", "p999_ms",
                      "mean_ms", "n"}
    assert p["n"] == 500
    assert len(m.lat[SEARCH].values()) == 32


def test_insert_backpressure_exhaustion_counts_drops(rng, caplog):
    base = make_clustered(rng, 400, DIM)
    eng = ServeEngine(SPFreshIndex.build(small_cfg(), base),
                      EngineConfig(max_insert_retries=2))

    def never_lands(vecs, vids, valid):
        return np.asarray(vids).copy(), np.zeros(len(vids), bool)

    eng.backend.insert = never_lands
    eng.backend.maintain = lambda budget: 0
    vecs = make_clustered(rng, 4, DIM)
    tk = eng.submit_insert(vecs, np.arange(4, dtype=np.int32))
    with caplog.at_level(logging.WARNING, logger="repro.serve"):
        ids, landed = tk.result()
    assert not landed.any()
    assert tk.dropped == 4                 # per-ticket accounting
    assert eng.metrics.insert_dropped == 4
    assert any("backpressure exhausted" in r.message for r in caplog.records)
