"""Insert backpressure splits the full posting it hit.

A row is refused when its primary (nearest) posting is at hard capacity.
The retry's maintenance round must split that posting first: under the
``drift`` ranking other oversized postings can outrank it for every round
the retries allow, so a round left to its ranking alone never frees the
room the row needs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lire
from repro.core.index import SPFreshIndex
from repro.serve.engine import EngineConfig, LocalBackend, ServeEngine
from repro.storage.wal import WalSet
from tests.test_lire import small_cfg

DIM = 16
JOBS = 2
RETRIES = 2
ROW_VID = 5_000


def _scenario():
    """``(state, row, target)``: posting ``target`` is full and the row's
    primary; seven postings over ``split_limit`` with a heavy probe
    history outrank it (more than ``JOBS × RETRIES``)."""
    rng = np.random.default_rng(5)
    centers = rng.normal(size=(10, DIM)).astype(np.float32) * 40
    base = np.concatenate(
        [c + rng.normal(size=(20, DIM)).astype(np.float32) for c in centers]
    )
    cfg = small_cfg(replica_count=1, maintain_policy="drift",
                    maintain_alpha=4.0, maintain_beta=0.0,
                    jobs_per_round=JOBS)
    state = SPFreshIndex.build(cfg, base).state
    nid = 1_000

    def fill(state, center, length):
        nonlocal nid
        _, pid = lire.navigate(state, jnp.asarray(center[None]), 1)
        pid = int(pid[0, 0])
        n = length - int(state.pool.posting_len[pid])
        x = center + 0.01 * rng.normal(size=(n, DIM)).astype(np.float32)
        state, landed, routes = lire.insert_batch(
            state, jnp.asarray(x), jnp.arange(nid, nid + n, dtype=jnp.int32),
            jnp.ones(n, bool))
        nid += n
        assert bool(landed.all()) and (np.asarray(routes)[:, 0] == pid).all()
        return state, pid

    others = []
    for c in centers[1:8]:
        state, pid = fill(state, c, cfg.split_limit + 2)
        others.append(pid)
    state, target = fill(state, centers[0], cfg.posting_capacity)
    access = np.zeros(cfg.num_postings_cap, np.int32)
    access[others] = 1000
    state = state.replace(telemetry=state.telemetry.replace(
        access_count=jnp.asarray(access)))
    row = centers[0] + 0.01 * rng.normal(size=(1, DIM)).astype(np.float32)
    return state, row, target


def _copy(state):
    return jax.tree.map(jnp.copy, state)


def _engine(backend):
    # no background slot: every round the row sees is a backpressure one
    return ServeEngine(backend, EngineConfig(
        max_insert_retries=RETRIES, maintain_budget=JOBS, policy="backlog",
        backlog_threshold=10**9,
    ))


def _live_vids(state) -> set[int]:
    vids = np.asarray(state.pool.block_vid)
    live = np.asarray(lire._page_slot_live(
        state, jnp.arange(vids.shape[0], dtype=jnp.int32))[1])
    return set(vids[live].tolist())


def test_backpressure_splits_the_full_primary_first():
    state, row, target = _scenario()
    # the ranking alone: every round the retries allow splits other
    # postings, and the row is still refused after them
    ranked = _copy(state)
    for _ in range(RETRIES):
        ranked, _ = lire.maintenance_round(ranked, JOBS)
    assert bool(ranked.centroid_valid[target])
    _, landed, routes = lire.insert_batch(
        ranked, jnp.asarray(row), jnp.asarray([ROW_VID], jnp.int32),
        jnp.ones(1, bool))
    assert not bool(landed[0]) and int(routes[0, 0]) == target

    eng = _engine(SPFreshIndex(state))
    _, landed = eng.submit_insert(
        row, np.asarray([ROW_VID], np.int32)).result()
    assert landed.all()
    assert eng.metrics.insert_retries == 1          # the first retry lands
    assert eng.metrics.insert_dropped == 0
    assert eng.report()["counters"]["insert.forced_splits"] == 1
    assert not bool(eng.backend.index.state.centroid_valid[target])


@pytest.mark.parametrize("wal", ["request", "dispatch"])
def test_backpressure_replay_gives_the_same_live_set(tmp_path, wal):
    """Replaying the insert rebuilds the live set the engine left: through
    ``SPFreshIndex.insert``'s own backpressure from the request-level WAL,
    and bit for bit from the dispatch-level WAL, whose maintenance
    records carry the postings each round split first."""
    state, row, _ = _scenario()
    twin = _copy(state)
    if wal == "request":
        idx = SPFreshIndex(state, wal_path=str(tmp_path / "wal"))
        idx.snapshot(str(tmp_path / "snap"))
        backend = LocalBackend(idx)
    else:
        backend = LocalBackend(SPFreshIndex(state))
        backend.attach_durability(WalSet(str(tmp_path / "wal"), 1))
    eng = _engine(backend)
    _, landed = eng.submit_insert(
        row, np.asarray([ROW_VID], np.int32)).result()
    assert landed.all() and eng.metrics.insert_retries == 1
    live = _live_vids(backend.index.state)
    assert ROW_VID in live

    if wal == "request":
        backend.index.wal.close()
        replayed = SPFreshIndex.restore(
            str(tmp_path / "snap"), state.cfg,
            wal_path=str(tmp_path / "wal")).state
    else:
        backend.wal_set.close()
        again = LocalBackend(SPFreshIndex(twin))
        again.replay(WalSet(str(tmp_path / "wal"), 1).recover_records())
        replayed = again.index.state
        for a, b in zip(jax.tree.leaves(backend.index.state),
                        jax.tree.leaves(replayed)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert _live_vids(replayed) == live


@pytest.mark.parametrize("named", ["nothing", "a posting under the limit"])
def test_force_naming_no_oversized_posting_leaves_the_round_unchanged(named):
    """Background rounds carry a force list too (all -1): it, or a name
    of a posting not over ``split_limit``, must select exactly the jobs
    the ranking does alone, down to every state leaf."""
    state, _, _ = _scenario()
    force = np.full(JOBS, -1, np.int32)
    if named != "nothing":
        lens = np.asarray(state.pool.posting_len)
        valid = np.asarray(state.centroid_valid)
        force[0] = np.flatnonzero(valid & (lens <= state.cfg.split_limit))[0]
    want, did0 = lire.maintenance_round(state, JOBS)
    got, did1 = lire.maintenance_round(state, JOBS, None, jnp.asarray(force))
    assert int(did0) == int(did1) == JOBS
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
