"""DurableBackend — the one durability lifecycle both index backends mix
in (paper §4.4 promoted into the `IndexBackend` protocol).

The lifecycle invariants live HERE exactly once: the not-while-replaying
WAL logging guard, applied-seqno bookkeeping, checkpoint = snapshot
(stamping per-shard ``wal_seqnos`` + the replay-critical ``lire_config``)
then WAL truncate, and the replay loop that re-applies a dispatch stream
through the subclass's ``_apply_record``.  Backends supply only what
differs: the state pytree to snapshot, manifest extras, the per-op
dispatch arms, and the shard count.

Checkpoints go through :class:`~repro.storage.snapshot.SnapshotStore`:
``checkpoint(dir)`` writes a full **base** unit (which is also the chain
compaction — the in-memory state already equals base + deltas + dirty
tail, so folding is a fresh full write that prunes the old chain), while
``checkpoint(dir, delta=True)`` writes a **delta** unit holding only the
blocks the pool's dirty bitmap marked since the previous unit, one file
per shard.  Either way the backend's in-memory state is swapped for the
dirty-cleared twin afterwards, so the next delta starts from a clean
ledger, and the WALs restart empty only after the unit commits.
"""
from __future__ import annotations

import dataclasses

from repro.storage.blockpool import clear_dirty
from repro.storage.snapshot import SnapshotStore


class DurableBackend:
    """Mixin for backends with dispatch-level WAL + snapshot recovery.

    Subclass hooks:
      * ``_snapshot_state()``  — the pytree the checkpoint serializes
      * ``_set_snapshot_state(state)`` — install the dirty-cleared state
      * ``_snapshot_extra()``  — backend-specific manifest fields
      * ``_apply_record(rec)`` — re-run one WAL dispatch (replay arms)
      * ``_wal_shards``        — logs in the WalSet (1 for local)
      * ``_lire_config()``     — config stamped into the manifest
    """

    wal_set = None
    _wal_applied = -1
    _replaying = False
    _repl_sink = None

    # ------------------------- subclass hooks --------------------------
    def _snapshot_state(self):
        raise NotImplementedError

    def _set_snapshot_state(self, state) -> None:
        raise NotImplementedError

    def _snapshot_extra(self) -> dict:
        return {}

    def _apply_record(self, rec) -> None:
        raise NotImplementedError

    def _lire_config(self):
        raise NotImplementedError

    @property
    def _wal_shards(self) -> int:
        return 1

    # ------------------------- the lifecycle ---------------------------
    def _log(self, op: str, payload: dict) -> None:
        if self._replaying:
            return
        if self.wal_set is not None:
            self._wal_applied = self.wal_set.append(op, payload)
        if self._repl_sink is not None:
            if self.wal_set is None:
                # Ephemeral service: no durable log, but replicas still
                # need a contiguous dispatch stream — mint local seqnos.
                self._wal_applied += 1
            self._repl_sink.publish(self._wal_applied, op, payload)

    def attach_replication(self, sink) -> None:
        """``sink.publish(seqno, op, payload)`` is called for every logged
        update dispatch, AFTER the WAL append assigns its seqno (so a
        published record is already durable when durability is on).  The
        sink must be cheap and non-blocking: it runs on the serialized
        pump thread, upstream of the ack point."""
        self._repl_sink = sink

    def attach_durability(self, wal_set, applied_seqno: int | None = None,
                          ) -> None:
        """``applied_seqno`` is the seqno this backend's state already
        reflects — the snapshot manifest stamp on recovery.  The default
        (last durable record) is ONLY correct when the state genuinely
        includes everything on disk (a fresh build about to checkpoint);
        recovery paths must pass the stamp or a later checkpoint would
        mark the unreplayed tail as applied."""
        assert wal_set.n_shards == self._wal_shards, (
            wal_set.n_shards, self._wal_shards,
        )
        self.wal_set = wal_set
        self._wal_applied = (
            applied_seqno if applied_seqno is not None
            else wal_set.next_seqno - 1
        )

    def wal_seqnos(self) -> list[int]:
        """Applied WAL seqno per shard (the snapshot manifest entry).
        The snapshot is one atomic commit, so shards advance together."""
        return [self._wal_applied] * self._wal_shards

    def wal_sync(self) -> None:
        """Force any group-commit-buffered WAL records durable — the ack
        point the service crosses before returning an update."""
        if self.wal_set is not None:
            self.wal_set.sync()

    def checkpoint(self, snapshot_dir: str, *, delta: bool = False) -> None:
        """Atomic snapshot unit stamping the applied WAL seqnos and the
        replay-critical config; the WALs restart empty only after the
        unit commit.  ``delta=True`` writes an incremental unit (dirty
        blocks + non-block leaves, per shard) chained onto the store's
        head; it silently promotes to a full base when no chain exists
        yet.  Afterwards the in-memory state is the dirty-cleared twin."""
        if self.wal_set is not None:
            self.wal_set.sync()    # buffered records precede the stamp
        store = SnapshotStore(snapshot_dir)
        state = self._snapshot_state()
        cleared = state.replace(pool=clear_dirty(state.pool))
        extra = {
            "wal_seqnos": self.wal_seqnos(),
            "lire_config": dataclasses.asdict(self._lire_config()),
            **self._snapshot_extra(),
        }
        if delta and store.has_base():
            store.save_delta(state, n_shards=self._wal_shards, extra=extra)
        else:
            store.save_base(cleared, extra=extra)
        self._set_snapshot_state(cleared)
        if self.wal_set is not None:
            self.wal_set.truncate()

    def replay(self, records, after_seqno: int = -1) -> int:
        """Re-apply a WAL dispatch stream through the backend's own
        jitted entry points; returns how many records were applied."""
        n = 0
        self._replaying = True
        try:
            for rec in records:
                if rec.seqno <= after_seqno:
                    continue
                self._apply_record(rec)
                self._wal_applied = rec.seqno
                n += 1
        finally:
            self._replaying = False
        return n

    def close(self) -> None:
        if self.wal_set is not None:
            self.wal_set.close()


# Geometry/protocol fields that must match between a snapshot and the
# opening spec: they shape the state pytree or change update-dispatch
# semantics, so replay under a different value is undefined.  Every
# LireConfig field is classified here or in REPLAY_EXEMPT_FIELDS below —
# the spflint replay pass (SPF104/105) cross-checks both lists against
# the config class and against every field read reachable from the
# jit-step builders, so a new field cannot ship unclassified.
REPLAY_CRITICAL_FIELDS = (
    "dim", "block_size", "max_blocks_per_posting", "num_blocks",
    "num_postings_cap", "num_vectors_cap", "vector_dtype",
    "split_limit", "merge_limit", "merge_fanout",
    "reassign_range", "reassign_budget", "replica_count", "replica_rng",
    "kmeans_iters", "enable_split", "enable_merge", "enable_reassign",
    # Job SELECTION shapes which postings every logged maintenance round
    # touches, so replaying under a different policy/weighting diverges.
    "maintain_policy", "maintain_alpha", "maintain_beta",
    # The payload codec changes the hot-tier dtype/leaf structure and the
    # rerank factor changes which candidates a logged search would have
    # returned; both are stamped by name so pre-codec snapshots (which
    # never stamped them) still pass.
    "codec", "rerank_factor",
    # Insert/reassign ROUTING runs through `lire.navigate`, whose kernel
    # path (Pallas nav vs XLA oracle) this selects.  The paths are
    # numerically equivalent only up to top-k tie-breaking on equal
    # distances — enough to route a vector to a different posting on
    # replay — so it must match the snapshot.  Stamped by name:
    # snapshots from before this stamp never recorded it and still pass.
    # Whether a kernel runs compiled or interpreted is the platform's
    # choice (repro.kernels.backend), not config: a snapshot's stale
    # "pallas_interpret" stamp is ignored.
    "use_pallas_nav",
)

# Serving-side fields a reopened index may change freely: they only
# shape dispatches that are never WAL-logged (searches) or whose logged
# records carry the value they ran with.  Each entry needs a reason —
# the replay pass treats this list as load-bearing, not a dumping
# ground.
REPLAY_EXEMPT_FIELDS = (
    # Search-path only; search dispatches are not WAL-logged.
    "nprobe", "scan_dtype", "use_pallas_scan", "scan_schedule",
    "scan_page_budget",
    # Logged "maintain"/"drain" records carry their own job counts, so
    # replay re-runs the original round shapes regardless of the
    # reopened config's default.
    "jobs_per_round",
)


def check_replay_config(manifest: dict, cfg, *, n_shards: int | None = None,
                        ) -> None:
    """Raise a clear error when a snapshot was written under a different
    replay-critical config than the spec now opening it (e.g. the serve
    launcher re-run with different sizing flags or a different
    ``--shards``) — BEFORE template construction turns the drift into a
    cryptic leaf-shape mismatch."""
    extra = manifest.get("extra", {})
    diffs = []
    if n_shards is not None:
        stamped_shards = extra.get("n_shards", 1)
        if stamped_shards != n_shards:
            diffs.append(
                f"n_shards: snapshot={stamped_shards!r} spec={n_shards!r}"
            )
    stamped = extra.get("lire_config")
    if stamped is None and not diffs:
        return  # pre-stamp snapshot: nothing to validate against
    if stamped is not None:
        now = dataclasses.asdict(cfg)
        diffs += [
            f"{f}: snapshot={stamped[f]!r} spec={now[f]!r}"
            for f in REPLAY_CRITICAL_FIELDS
            if f in stamped and stamped[f] != now[f]
        ]
    if diffs:
        raise ValueError(
            "snapshot was written under a different index config; "
            "recovery must reuse the original geometry/protocol "
            "parameters (re-run with the original sizing flags or point "
            "DurabilitySpec at a fresh root).  Mismatched fields:\n  "
            + "\n  ".join(diffs)
        )
