"""Crash recovery (paper §4.4): snapshot + WAL replay."""
import os
import struct

import numpy as np
import pytest

from repro.core.index import SPFreshIndex
from repro.core.types import LireConfig
from repro.storage.wal import (
    WalCorruptionError, WalSet, WriteAheadLog, iter_wal,
)
from tests.conftest import make_clustered
from tests.test_lire import small_cfg


def test_wal_roundtrip(tmp_path):
    path = str(tmp_path / "wal.log")
    wal = WriteAheadLog(path)
    wal.append("insert", {"vecs": np.ones((2, 4), np.float32), "vids": np.asarray([1, 2])})
    wal.append("delete", {"vids": np.asarray([7])})
    wal.close()
    recs = list(iter_wal(path))
    assert [r.op for r in recs] == ["insert", "delete"]
    np.testing.assert_array_equal(recs[0].payload["vids"], [1, 2])
    assert recs[0].seqno == 0 and recs[1].seqno == 1


def test_wal_tolerates_torn_tail(tmp_path):
    path = str(tmp_path / "wal.log")
    wal = WriteAheadLog(path)
    wal.append("delete", {"vids": np.asarray([1])})
    wal.close()
    with open(path, "ab") as fh:
        fh.write(b"SPFW\x99\x00\x00\x00partial")  # torn record
    recs = list(iter_wal(path))
    assert len(recs) == 1


def _record_offsets(blob: bytes) -> list[int]:
    """Start offset of every record in an encoded WAL image."""
    offsets, pos = [], 0
    while pos < len(blob):
        _, length = struct.unpack_from("<4sI", blob, pos)
        offsets.append(pos)
        pos += 8 + length
    return offsets


def test_wal_torn_tail_property_every_byte_offset(tmp_path):
    """Truncating the log at EVERY byte offset of the last record must
    yield exactly the earlier records — the crash-mid-append property the
    recovery path relies on (torn tail = op never acknowledged)."""
    path = str(tmp_path / "wal.log")
    wal = WriteAheadLog(path)
    for i in range(3):
        wal.append("insert", {
            "vecs": np.full((4, 8), i, np.float32),
            "vids": np.arange(4, dtype=np.int32) + 10 * i,
        })
    wal.close()
    with open(path, "rb") as fh:
        blob = fh.read()
    last_start = _record_offsets(blob)[-1]
    trunc = str(tmp_path / "trunc.log")
    for cut in range(last_start, len(blob)):
        with open(trunc, "wb") as fh:
            fh.write(blob[:cut])
        recs = list(iter_wal(trunc))
        assert [r.seqno for r in recs] == [0, 1], f"cut at byte {cut}"
    assert [r.seqno for r in iter_wal(path)] == [0, 1, 2]


def test_wal_midfile_magic_mismatch_raises(tmp_path):
    """A fully-written header with bad magic is corruption, not a tail —
    silently truncating there would drop acknowledged (fsync'd) records."""
    path = str(tmp_path / "wal.log")
    wal = WriteAheadLog(path)
    for i in range(3):
        wal.append("delete", {"vids": np.asarray([i])})
    wal.close()
    with open(path, "rb") as fh:
        blob = fh.read()
    mid = _record_offsets(blob)[1]
    corrupt = bytearray(blob)
    corrupt[mid:mid + 4] = b"XXXX"
    with open(path, "wb") as fh:
        fh.write(bytes(corrupt))
    with pytest.raises(WalCorruptionError):
        list(iter_wal(path))
    with pytest.raises(WalCorruptionError):
        WriteAheadLog(path)


def test_wal_garbage_magic_at_tail_is_a_tear_not_corruption(tmp_path):
    """A multi-page append can persist later pages without the first
    (no prefix ordering before fsync), leaving garbage where the final
    record's header should be.  That is an UNACKNOWLEDGED tail — it must
    be trimmed, not raised, or a normal crash bricks recovery."""
    path = str(tmp_path / "wal.log")
    wal = WriteAheadLog(path)
    wal.append("delete", {"vids": np.asarray([1])})
    wal.append("delete", {"vids": np.asarray([2])})
    wal.close()
    with open(path, "ab") as fh:
        fh.write(b"\x00GARBAGE\x00" * 40)       # bad magic, no record after
    assert [r.seqno for r in iter_wal(path)] == [0, 1]
    wal2 = WriteAheadLog(path)                  # trims the garbage tail
    wal2.append("delete", {"vids": np.asarray([3])})
    wal2.close()
    assert [r.seqno for r in iter_wal(path)] == [0, 1, 2]


def test_wal_reopen_trims_torn_tail_then_appends(tmp_path):
    """Reopening a log with a torn tail must trim it — otherwise new
    appends land after the garbage and the reader never sees them."""
    path = str(tmp_path / "wal.log")
    wal = WriteAheadLog(path)
    wal.append("delete", {"vids": np.asarray([1])})
    wal.append("delete", {"vids": np.asarray([2])})
    wal.close()
    size = os.path.getsize(path)
    with open(path, "ab") as fh:
        fh.write(b"SPFW\x99\x00\x00\x00partial")   # torn record
    wal2 = WriteAheadLog(path)
    assert os.path.getsize(path) == size           # tail trimmed
    wal2.append("delete", {"vids": np.asarray([3])})
    wal2.close()
    assert [r.seqno for r in iter_wal(path)] == [0, 1, 2]


def test_wal_append_is_immediately_durable(tmp_path):
    """The fsync-per-append contract: a record must be readable through a
    fresh file handle the moment append() returns (no close/flush)."""
    path = str(tmp_path / "wal.log")
    wal = WriteAheadLog(path)
    wal.append("insert", {"vecs": np.ones((2, 4), np.float32),
                          "vids": np.asarray([5, 6])})
    recs = list(iter_wal(path))      # separate fd, wal still open
    assert len(recs) == 1 and recs[0].seqno == 0
    wal.close()


def test_walset_resyncs_lagging_shard_logs(tmp_path):
    """A crash can tear the per-shard logs at different records; recovery
    takes the longest clean log as authoritative and re-syncs the rest."""
    ws = WalSet(str(tmp_path / "wal"), 3)
    for i in range(4):
        ws.append("delete", {"vids": np.asarray([i])})
    ws.close()
    # shard 1 lost its last record, shard 2 its last two (torn at the
    # record boundary = fsync'd on shard 0 only)
    for shard, keep in ((1, 3), (2, 2)):
        path = ws.shard_path(shard)
        with open(path, "rb") as fh:
            blob = fh.read()
        cut = _record_offsets(blob)[keep]
        with open(path, "wb") as fh:
            fh.write(blob[:cut])
    ws2 = WalSet(str(tmp_path / "wal"), 3)
    recs = ws2.recover_records()
    assert [r.seqno for r in recs] == [0, 1, 2, 3]
    assert ws2.last_seqnos() == [3, 3, 3]
    for shard in range(3):           # every log re-synced on disk
        assert [r.seqno for r in iter_wal(ws2.shard_path(shard))] == [0, 1, 2, 3]
    assert ws2.append("delete", {"vids": np.asarray([9])}) == 4
    ws2.close()


def test_walset_salvages_one_corrupt_log_from_clean_replicas(tmp_path):
    """Mid-file corruption in ONE shard log must not brick recovery when
    clean replicas exist: the corrupt log is repaired from the longest
    readable stream.  Only all-logs-corrupt raises."""
    ws = WalSet(str(tmp_path / "wal"), 3)
    for i in range(4):
        ws.append("delete", {"vids": np.asarray([i])})
    ws.close()
    path1 = ws.shard_path(1)
    with open(path1, "rb") as fh:
        blob = fh.read()
    mid = _record_offsets(blob)[1]
    corrupt = bytearray(blob)
    corrupt[mid:mid + 4] = b"XXXX"
    with open(path1, "wb") as fh:
        fh.write(bytes(corrupt))
    ws2 = WalSet(str(tmp_path / "wal"), 3)       # salvage, no raise
    recs = ws2.recover_records()
    assert [r.seqno for r in recs] == [0, 1, 2, 3]
    assert [r.seqno for r in iter_wal(path1)] == [0, 1, 2, 3]  # repaired
    ws2.close()
    # single-log set (local backend): corruption has no replica to heal
    # from and must surface
    ws3 = WalSet(str(tmp_path / "wal1"), 1)
    ws3.append("delete", {"vids": np.asarray([0])})
    ws3.append("delete", {"vids": np.asarray([1])})
    ws3.close()
    p = ws3.shard_path(0)
    with open(p, "rb") as fh:
        blob = fh.read()
    corrupt = bytearray(blob)
    corrupt[0:4] = b"XXXX"
    with open(p, "wb") as fh:
        fh.write(bytes(corrupt))
    with pytest.raises(WalCorruptionError):
        WalSet(str(tmp_path / "wal1"), 1)


def test_snapshot_swap_never_leaves_no_snapshot(tmp_path, rng):
    """save_snapshot rotates the old snapshot aside before the new one
    commits; a crash between the two renames leaves ``path.old``, which
    snapshot_exists/load_snapshot resolve — never zero snapshots."""
    from repro.storage.snapshot import (
        load_snapshot, save_snapshot, snapshot_exists,
    )

    snap = str(tmp_path / "snap")
    state = {"x": np.arange(4, dtype=np.float32),
             "y": np.ones((2, 2), np.float32)}
    save_snapshot(snap, state, extra={"gen": 1})
    save_snapshot(snap, state, extra={"gen": 2})
    assert not os.path.exists(snap + ".old")     # happy path cleans up
    # crash window: the previous snapshot was rotated aside but the new
    # one never landed
    os.replace(snap, snap + ".old")
    assert snapshot_exists(snap)
    _, manifest = load_snapshot(snap, state)
    assert manifest["extra"]["gen"] == 2
    # and the next save must not delete the fallback before its own
    # commit: even simulating a crash right before that commit (the .old
    # is all there is), a snapshot remains loadable
    assert snapshot_exists(snap)
    save_snapshot(snap, state, extra={"gen": 3})
    _, manifest = load_snapshot(snap, state)
    assert manifest["extra"]["gen"] == 3
    assert not os.path.exists(snap + ".old")


def test_snapshot_then_wal_replay_recovers(tmp_path, rng):
    cfg = small_cfg()
    base = make_clustered(rng, 500, 16, n_clusters=4)
    wal_path = str(tmp_path / "wal.log")
    snap_path = str(tmp_path / "snap")

    idx = SPFreshIndex.build(cfg, base, wal_path=wal_path)
    idx.snapshot(snap_path)

    # Updates after the snapshot — these live only in the WAL.
    extra = make_clustered(rng, 60, 16, n_clusters=2)
    ids = np.arange(6000, 6060, dtype=np.int32)
    idx.insert(extra, ids)
    idx.delete(np.asarray([3, 4], np.int32))
    want_d, want_v = idx.search(extra[:8], 5)

    # "Crash": rebuild from snapshot + WAL.
    rec = SPFreshIndex.restore(snap_path, cfg, wal_path=wal_path)
    got_d, got_v = rec.search(extra[:8], 5)
    np.testing.assert_array_equal(want_v, got_v)
    np.testing.assert_allclose(want_d, got_d, rtol=1e-5)
    # Deleted stay deleted.
    _, got = rec.search(base[3:4], 5)
    assert 3 not in got[0].tolist()


def test_snapshot_truncates_wal(tmp_path, rng):
    cfg = small_cfg()
    base = make_clustered(rng, 300, 16)
    wal_path = str(tmp_path / "wal.log")
    idx = SPFreshIndex.build(cfg, base, wal_path=wal_path)
    idx.insert(base[:4], np.arange(1000, 1004, dtype=np.int32))
    assert os.path.getsize(wal_path) > 0
    idx.snapshot(str(tmp_path / "snap"))
    assert len(list(iter_wal(wal_path))) == 0


def test_restore_without_snapshot_replays_full_wal(tmp_path, rng):
    cfg = small_cfg()
    wal_path = str(tmp_path / "wal.log")
    # Start from an EMPTY index: build 0 postings is degenerate; instead use
    # a small build then log inserts.
    base = make_clustered(rng, 200, 16)
    idx = SPFreshIndex.build(cfg, base, wal_path=wal_path)
    extra = make_clustered(rng, 20, 16)
    idx.insert(extra, np.arange(7000, 7020, dtype=np.int32))
    # No snapshot: restoring from scratch replays the WAL over the template —
    # only the WAL'd updates come back (build state is not in the WAL).
    rec = SPFreshIndex.restore(str(tmp_path / "nosnap"), cfg, wal_path=wal_path)
    assert rec._wal_applied == idx._wal_applied


# ---------------------------------------------------------------------------
# Delta snapshot chain (SnapshotStore)
# ---------------------------------------------------------------------------

def _tiny_cfg():
    return LireConfig(
        dim=8, block_size=4, max_blocks_per_posting=4, num_blocks=128,
        num_postings_cap=32, num_vectors_cap=1024, split_limit=12,
        merge_limit=2, replica_count=2, nprobe=4,
    )


def _evolve_states(rng, n_steps=3):
    """A build + a few update batches; returns the per-checkpoint states
    with the dirty ledger cleared exactly as the backends do."""
    import jax.numpy as jnp
    from repro.core import lire
    from repro.core.index import build_state
    from repro.storage import blockpool as bp

    cfg = _tiny_cfg()
    base = make_clustered(rng, 120, 8, n_clusters=4)
    state = build_state(cfg, base)
    state = state.replace(pool=bp.clear_dirty(state.pool))
    states = [state]
    nid = 200
    for step in range(n_steps):
        vecs = make_clustered(rng, 12, 8, n_clusters=2)
        state, _, _ = lire.insert_batch(
            state, jnp.asarray(vecs),
            jnp.arange(nid, nid + 12, dtype=jnp.int32), jnp.ones(12, bool),
        )
        state = lire.delete_batch(
            state, jnp.arange(nid, nid + 3, dtype=jnp.int32),
            jnp.ones(3, bool),
        )
        nid += 12
        states.append(state)           # dirty ledger still set: delta input
        state = state.replace(pool=bp.clear_dirty(state.pool))
        states[-1] = (states[-1], state)   # (delta input, cleared twin)
    return cfg, states


def _assert_states_equal(a, b):
    import jax

    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_snapshot_store_delta_chain_roundtrip(tmp_path, rng):
    """base → delta → delta restores the exact final state (blocks folded
    block-by-block, dense leaves overwritten, dirty ledger reset)."""
    from repro.core.types import make_empty_state
    from repro.storage.snapshot import SnapshotStore

    cfg, states = _evolve_states(rng)
    store = SnapshotStore(str(tmp_path / "snap"))
    store.save_base(states[0], extra={"wal_seqnos": [0]})
    for i, (dirty_state, _cleared) in enumerate(states[1:], start=1):
        store.save_delta(dirty_state, extra={"wal_seqnos": [i]})
    assert store.chain_len() == len(states) - 1
    got, manifest = store.load(make_empty_state(cfg))
    assert manifest["extra"]["wal_seqnos"] == [len(states) - 1]
    _assert_states_equal(got, states[-1][1])   # == final cleared state
    # a delta is much smaller than the base it chains to
    head_bytes = store.unit_bytes()
    base_bytes = store.unit_bytes(store._chain(store._head())[0])
    assert head_bytes < 0.5 * base_bytes, (head_bytes, base_bytes)


def test_snapshot_store_compaction_folds_and_prunes(tmp_path, rng):
    from repro.core.types import make_empty_state
    from repro.storage.snapshot import SnapshotStore

    cfg, states = _evolve_states(rng)
    store = SnapshotStore(str(tmp_path / "snap"))
    store.save_base(states[0])
    for dirty_state, _ in states[1:]:
        store.save_delta(dirty_state)
    final = states[-1][1]
    store.save_base(final)                      # the compaction fold
    assert store.chain_len() == 0
    units = store._units()
    assert len(units) == 1 and units[0].startswith("base-")
    got, _ = store.load(make_empty_state(cfg))
    _assert_states_equal(got, final)


def test_snapshot_store_crash_at_every_fold_step(tmp_path, rng):
    """Kill the store at EVERY crash point of the base→delta→compaction
    lifecycle; after each kill a fresh SnapshotStore must still resolve a
    complete recovery point equal to the last committed logical state.
    (The torn-tail harness's discipline applied to the snapshot chain.)"""
    from repro.core.types import make_empty_state
    from repro.storage import snapshot as snap_mod
    from repro.storage.snapshot import SnapshotStore

    cfg, states = _evolve_states(rng)
    template = make_empty_state(cfg)
    final = states[-1][1]

    class Boom(Exception):
        pass

    def run_lifecycle(store):
        """(label, expected-state-after-commit) steps of the lifecycle."""
        store.save_base(states[0])
        yield "base"
        for i, (dirty_state, cleared) in enumerate(states[1:]):
            store.save_delta(dirty_state)
            yield f"delta{i}"
        store.save_base(final)                  # compaction
        yield "compact"

    # Pass 1: count the crash points of each lifecycle stage.
    labels = []
    snap_mod._crash_hook = lambda label: labels.append(label)
    try:
        root0 = str(tmp_path / "count")
        for _ in run_lifecycle(SnapshotStore(root0)):
            pass
    finally:
        snap_mod._crash_hook = None
    n_points = len(labels)
    assert n_points >= 8, f"expected several crash points, saw {labels}"

    # Pass 2: for every k, crash at the k-th point and assert recovery.
    committed = {  # stage completed before the crash → expected state
        "start": states[0], "base": states[0], "compact": final,
    }
    for i, (_d, cleared) in enumerate(states[1:]):
        committed[f"delta{i}"] = cleared
    for k in range(1, n_points + 1):
        calls = {"n": 0}

        def hook(label, _k=k):
            calls["n"] += 1
            if calls["n"] == _k:
                raise Boom(label)

        root = str(tmp_path / f"crash_{k}")
        store = SnapshotStore(root)
        done = "start"
        snap_mod._crash_hook = hook
        try:
            for stage in run_lifecycle(store):
                done = stage
        except Boom:
            pass
        finally:
            snap_mod._crash_hook = None
        reopened = SnapshotStore(root)
        if done == "start" and not reopened.exists():
            continue  # crashed before the very first commit: empty root
        got, _ = reopened.load(template)
        want = committed[done]
        try:
            _assert_states_equal(got, want)
        except AssertionError:
            # a crash AFTER the unit commit but before cleanup may
            # already expose the next stage — equally valid (the WAL is
            # truncated only after save returns, and replay is
            # idempotent past the stamped seqno)
            stages = ["start", "base"] + [
                f"delta{i}" for i in range(len(states) - 1)
            ] + ["compact"]
            nxt = stages[stages.index(done) + 1]
            _assert_states_equal(got, committed[nxt])


def test_snapshot_store_reads_legacy_full_snapshot(tmp_path, rng):
    """A durable root written by the pre-chain code (manifest.json at the
    store root, one leaf short of today's pool) must load: the missing
    dirty ledger is migrated in as all-clean, and the first save_base
    converts the root to the chained layout."""
    import jax
    from repro.core.types import make_empty_state
    from repro.storage.snapshot import SnapshotStore, _dirty_leaf_index
    import json as json_mod

    cfg, states = _evolve_states(rng, n_steps=1)
    final = states[-1][1]
    leaves = jax.tree_util.tree_leaves(final)
    di = _dirty_leaf_index(final)
    legacy = [np.asarray(x) for i, x in enumerate(leaves) if i != di]
    root = tmp_path / "snap"
    root.mkdir()
    np.savez(root / "leaves.npz",
             **{f"leaf_{i}": a for i, a in enumerate(legacy)})
    (root / "manifest.json").write_text(json_mod.dumps(
        {"n_leaves": len(legacy), "step": 0, "extra": {"wal_seqnos": [5]}}
    ))
    store = SnapshotStore(str(root))
    assert store.exists() and not store.has_base()
    got, manifest = store.load(make_empty_state(cfg))
    assert manifest["extra"]["wal_seqnos"] == [5]
    _assert_states_equal(got, final)
    store.save_base(got)
    assert store.has_base()
    assert not (root / "manifest.json").exists()   # legacy files pruned


# ---------------------------------------------------------------------------
# WAL group commit + compaction
# ---------------------------------------------------------------------------

def test_wal_group_commit_batches_fsyncs(tmp_path):
    ws = WalSet(str(tmp_path / "wal"), 2)
    ws.set_group_commit(4)
    for i in range(10):
        ws.append("delete", {"vids": np.asarray([i])})
    # 10 appends → 2 full windows of 4; 2 records still pending
    assert ws.pending == 2
    assert ws.n_fsyncs == 2 * 2                 # 2 windows × 2 shard logs
    ws.sync()                                   # the ack point
    assert ws.pending == 0 and ws.n_fsyncs == 3 * 2
    ws.sync()                                   # clean sync is free
    assert ws.n_fsyncs == 3 * 2
    st = ws.stats()
    assert st["appends"] == 10
    assert st["fsyncs_per_append"] < 1.0
    # every record is readable post-sync
    assert [r.seqno for r in iter_wal(ws.shard_path(0))] == list(range(10))
    ws.close()


def test_wal_group_commit_off_syncs_every_append(tmp_path):
    ws = WalSet(str(tmp_path / "wal"), 1)
    for i in range(5):
        ws.append("delete", {"vids": np.asarray([i])})
    assert ws.pending == 0 and ws.n_fsyncs == 5
    ws.close()


def test_compact_wal_records_drops_dead_insert_rows():
    from repro.storage.wal import WalRecord, compact_wal_records

    def ins(seq, vids, valid=None):
        vids = np.asarray(vids, np.int32)
        return WalRecord("insert", {
            "vecs": np.zeros((len(vids), 4), np.float32), "vids": vids,
            "valid": (np.ones(len(vids), bool) if valid is None
                      else np.asarray(valid, bool)),
        }, seq)

    def dele(seq, vids):
        vids = np.asarray(vids, np.int32)
        return WalRecord("delete", {
            "vids": vids, "valid": np.ones(len(vids), bool)}, seq)

    recs = [
        ins(0, [1, 2, 3]),
        dele(1, [2]),            # kills row vid=2 of record 0
        ins(2, [4, 5]),
        dele(3, [4, 5]),         # record 2 fully dead → dropped
        ins(4, [2]),             # REINSERT of 2 after its delete: kept
        WalRecord("maintain", {"jobs": np.asarray(4)}, 5),
    ]
    out, dropped = compact_wal_records(recs)
    assert dropped == 3          # vid2@0, vid4@2, vid5@2
    assert [r.seqno for r in out] == [0, 1, 3, 4, 5]
    np.testing.assert_array_equal(out[0].payload["valid"],
                                  [True, False, True])
    assert out[3].op == "insert"          # the reinsert survives intact
    np.testing.assert_array_equal(out[3].payload["valid"], [True])
    # deletes and maintains are never dropped
    assert [r.op for r in out] == [
        "insert", "delete", "delete", "insert", "maintain"]


# ---------------------------------------------------------------------------
# Replication stream (read replicas tail the dispatch log)
# ---------------------------------------------------------------------------

def _durable_backend(tmp_path, rng, n=400):
    """A LocalBackend with a WalSet attached — the replication primary."""
    from repro.serve.engine import LocalBackend

    cfg = small_cfg()
    base = make_clustered(rng, n, 16, n_clusters=4)
    backend = LocalBackend(SPFreshIndex.build(cfg, base))
    ws = WalSet(str(tmp_path / "wal"), 1)
    backend.attach_durability(ws)
    return backend, ws


def _pad_insert(backend, rng, vid0, n=8):
    vecs = make_clustered(rng, n, 16, n_clusters=2)
    backend.insert(vecs, np.arange(vid0, vid0 + n, dtype=np.int32),
                   np.ones(n, bool))


def test_replica_tails_live_wal_in_seqno_order(tmp_path, rng):
    """The async-replication contract over a LIVE WalSet tail: a replica
    that repeatedly replays ``iter_wal(path, after_seqno=cursor)`` while
    the primary keeps appending receives exactly the records past its
    cursor, contiguous and in seqno order, and converges to bit-parity
    every time it drains the tail."""
    from repro.distributed.replication import states_equal

    primary, ws = _durable_backend(tmp_path, rng)
    replica = primary.clone()                  # applied == primary (-1)
    path = ws.shard_path(0)
    for step in range(3):
        _pad_insert(primary, rng, 1000 + 100 * step)
        primary.delete(np.asarray([1000 + 100 * step], np.int32),
                       np.ones(1, bool))
        cursor = replica._wal_applied
        recs = list(iter_wal(path, after_seqno=cursor))
        assert [r.seqno for r in recs] == list(
            range(cursor + 1, primary._wal_applied + 1))
        replica.replay(recs, after_seqno=cursor)
        assert replica._wal_applied == primary._wal_applied
        assert states_equal(primary.index.state, replica.index.state)


def test_replica_replay_is_idempotent_on_redelivery(tmp_path, rng):
    """The window hands a replica at-least-once delivery: re-replaying
    records at or below the cursor (an overlapping read of the tail)
    must apply nothing and leave the state bit-identical."""
    from repro.distributed.replication import states_equal

    primary, ws = _durable_backend(tmp_path, rng)
    replica = primary.clone()
    for step in range(2):
        _pad_insert(primary, rng, 2000 + 100 * step)
    all_recs = list(iter_wal(ws.shard_path(0), after_seqno=-1))
    assert replica.replay(all_recs, after_seqno=replica._wal_applied) == 2
    before = replica.fork_state()
    # full redelivery, then an overlapping window: both no-ops
    assert replica.replay(all_recs, after_seqno=replica._wal_applied) == 0
    assert replica.replay(all_recs[-1:],
                          after_seqno=replica._wal_applied) == 0
    assert replica._wal_applied == primary._wal_applied
    assert states_equal(before, replica.index.state)
    assert states_equal(primary.index.state, replica.index.state)


def test_replica_catchup_from_snapshot_plus_tail(tmp_path, rng):
    """The window-overflow path: a replica too far behind adopts a fork
    of the primary at seqno S and replays only the tail past S —
    landing bit-identical to a replica that replayed everything."""
    from repro.distributed.replication import states_equal

    primary, ws = _durable_backend(tmp_path, rng)
    patient = primary.clone()                  # replays the full stream
    for step in range(2):
        _pad_insert(primary, rng, 3000 + 100 * step)
    fork, fork_seqno = primary.fork_state(), primary._wal_applied
    for step in range(2):                      # the tail past the fork
        _pad_insert(primary, rng, 4000 + 100 * step)

    late = primary.clone()
    late.adopt_state(fork)                     # snapshot catch-up
    late._wal_applied = fork_seqno
    tail = list(iter_wal(ws.shard_path(0), after_seqno=fork_seqno))
    assert [r.seqno for r in tail] == [fork_seqno + 1, fork_seqno + 2]
    late.replay(tail, after_seqno=fork_seqno)

    patient.replay(list(iter_wal(ws.shard_path(0), after_seqno=-1)),
                   after_seqno=patient._wal_applied)
    assert late._wal_applied == patient._wal_applied == primary._wal_applied
    assert states_equal(late.index.state, primary.index.state)
    assert states_equal(late.index.state, patient.index.state)


def test_compact_wal_records_leaves_sharded_streams_untouched():
    from repro.storage.wal import WalRecord, compact_wal_records

    recs = [
        WalRecord("insert", {"vecs": np.zeros((2, 4), np.float32),
                             "valid": np.ones(2, bool)}, 0),
        WalRecord("delete", {"handles": np.asarray([3, 9])}, 1),
    ]
    out, dropped = compact_wal_records(recs)
    assert dropped == 0 and [r.seqno for r in out] == [0, 1]
