"""ServiceSpec — the one declarative description of a SPFresh service.

Every knob the repo grew across `LireConfig`, `EngineConfig`,
`ShardedIndex.__init__` kwargs, and `launch.serve` flags lives in exactly
one frozen sub-spec here; `spfresh.open(spec)` compiles the spec into a
running :class:`~repro.api.service.Service` over either backend.  Adding a
knob is now a one-file change: extend the sub-spec, consume it in
``lire_config()`` / ``engine_config()`` — nothing else threads it.

Sub-specs (all frozen dataclasses, composable with ``dataclasses.replace``):

  * :class:`IndexSpec`       — the LIRE protocol + storage geometry
                               (wraps :class:`~repro.core.types.LireConfig`)
  * :class:`ScanSpec`        — the Pallas posting-scan data path flags
  * :class:`ServeSpec`       — micro-batching + maintenance policy
                               (compiles to ``EngineConfig``)
  * :class:`MaintenanceSpec` — Local-Rebuilder round shape / budget
  * :class:`DurabilitySpec`  — WAL dir, snapshot dir, checkpoint cadence
  * :class:`ShardSpec`       — mesh geometry for the sharded backend
"""
from __future__ import annotations

import dataclasses
import os

from repro.core.types import LireConfig


@dataclasses.dataclass(frozen=True)
class IndexSpec:
    """Index geometry + LIRE protocol parameters.

    ``config`` is the full :class:`LireConfig`; ``seed`` seeds the offline
    SPANN build.  Scan/maintenance fields of the config are *defaults* —
    the sibling :class:`ScanSpec` / :class:`MaintenanceSpec` override them
    (``ServiceSpec.lire_config()`` folds everything into one config).
    """

    config: LireConfig = LireConfig()
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class ScanSpec:
    """Posting-scan data path (PR 2's flags, spec-ified).

    ``None`` means "defer to ``IndexSpec.config``" for the tri-state
    flags; ``probe_chunk`` is an engine-side knob (oracle path only).
    """

    probe_chunk: int = 0
    use_pallas_scan: bool | None = None
    scan_schedule: str | None = None       # "per_query" | "batched" | None
    scan_page_budget: int | None = None
    # Posting payload codec (storage/codec.py): "fp32" | "bf16" | "int8";
    # None defers to IndexSpec.config.  Lossy codecs over-fetch
    # rerank_factor×k quantized candidates and rerank them against the
    # exact tier (see LireConfig.codec / .rerank_factor).
    codec: str | None = None
    rerank_factor: int | None = None


@dataclasses.dataclass(frozen=True)
class ServeSpec:
    """Micro-batching + maintenance scheduling (compiles to EngineConfig)."""

    search_k: int = 10
    nprobe: int | None = None
    max_batch: int = 256
    min_bucket: int = 8
    policy: str = "ratio"                  # "ratio" | "backlog"
    fg_bg_ratio: int = 2
    backlog_threshold: int = 1
    max_insert_retries: int = 4
    # --- async serving (background pump thread; see serve/engine.py) ---
    # async_serve=True: the engine owns a dedicated pump thread; callers
    # only enqueue and block on per-ticket events, maintenance runs in
    # queue-idle gaps, and durable update tickets ack after the WAL
    # fsync.  max_wait_ms is the batch-formation window: an unfenced
    # head run is held up to this long so micro-batches fill toward the
    # top bucket instead of dispatching immediately (async mode only).
    async_serve: bool = False
    max_wait_ms: float = 0.0
    # --- read replicas (serve/engine.py + distributed/replication.py) ---
    # With ShardSpec.n_replicas > 1 the pump routes search batches to
    # replica workers round-robin; max_lag is the freshness bound (a
    # replica more than max_lag WAL seqnos behind the primary is skipped
    # and the batch falls back to the primary), replica_inflight caps the
    # routed-but-unfinished batches a single replica may hold.
    max_lag: int = 64
    replica_inflight: int = 2


@dataclasses.dataclass(frozen=True)
class MaintenanceSpec:
    """Local-Rebuilder round shape.  ``None`` defers to IndexSpec.config."""

    jobs_per_round: int | None = None      # split/merge jobs per fused round
    merge_fanout: int | None = None
    reassign_budget: int | None = None
    maintain_budget: int | None = None     # jobs per background SLOT
                                           # (None -> jobs_per_round)
    # Job selection: "size" (top-K longest / bottom-K shortest — the
    # parity baseline) or "drift" (Ada-IVF-style cost model over the
    # per-posting access/update/drift telemetry).  None defers to
    # IndexSpec.config; alpha/beta weigh the access-rate and drift terms.
    policy: str | None = None              # "size" | "drift"
    alpha: float | None = None
    beta: float | None = None


@dataclasses.dataclass(frozen=True)
class DurabilitySpec:
    """Crash-recovery lifecycle: per-shard WAL + snapshot checkpoints.

    ``root=None`` disables durability (an ephemeral service).  With a
    root, every update dispatch is WAL-appended (fsync'd) before it runs,
    ``checkpoint()`` writes an atomic snapshot stamping each shard's
    applied WAL seqno and truncates the logs, and ``spfresh.open`` replays
    snapshot + WAL tails.  ``checkpoint_every=N`` auto-checkpoints (full
    base snapshot) after every N update rows (0 = manual/close only).

    The durability **fast path** (paper §4.4's block-granular
    copy-on-write):

    * ``delta_every=N`` — every N update rows, auto-checkpoint as a
      **delta** snapshot: only the blocks the pool's dirty bitmap marked
      since the last unit, one file per shard, chained to the base.
      Checkpoint bytes scale with churn, not index size.
    * ``compact_every=M`` — once M deltas stack on the base, the next
      delta-cadence checkpoint is promoted to a compaction: a fresh full
      base folds the chain and prunes it (0 = never auto-compact).
    * ``group_commit=N`` (+ ``group_commit_ms``) — batch up to N update
      dispatches per WAL fsync.  The ack point does not move: the service
      forces a sync before an update call returns, so one fsync covers
      every dispatch that ran inside the call (retries, interleaved
      maintenance, ``insert_bulk`` chunks).
    * ``compact_wal=True`` — on recovery, mask insert rows whose vids
      were later deleted before replaying (local backend; preserves the
      live set and version map, not the physical block layout — see
      ``storage.wal.compact_wal_records``).
    """

    root: str | None = None
    wal_dir: str | None = None             # default: <root>/wal
    snapshot_dir: str | None = None        # default: <root>/snapshot
    checkpoint_every: int = 0
    snapshot_on_open: bool = True          # durability point for the build
    checkpoint_on_close: bool = True
    # --- durability fast path ---
    delta_every: int = 0                   # rows per auto DELTA checkpoint
    compact_every: int = 16                # deltas per chain before re-base
    group_commit: int = 0                  # dispatches per WAL fsync window
    group_commit_ms: float = 0.0           # window age-out (0 = count only)
    compact_wal: bool = False              # replay-side WAL compaction

    @property
    def enabled(self) -> bool:
        return bool(self.root or (self.wal_dir and self.snapshot_dir))

    def resolved_wal_dir(self) -> str:
        assert self.enabled
        return self.wal_dir or os.path.join(self.root, "wal")

    def resolved_snapshot_dir(self) -> str:
        assert self.enabled
        return self.snapshot_dir or os.path.join(self.root, "snapshot")


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """Mesh geometry.  ``n_shards=1`` selects the single-host backend.

    ``n_replicas > 1`` adds a leading **data** axis holding N full copies
    of the index: the primary (replica 0) alone runs the WAL-append +
    dispatch order, and every logged dispatch is streamed to the other
    replicas through a bounded async queue replayed in seqno order (see
    ``distributed/replication.py``).  The model axis continues to shard
    postings exactly as before — replication composes with sharding, so
    ``n_replicas=2, n_shards=2`` needs a 4-device (data, model) mesh.
    """

    n_shards: int = 1
    shard_axes: tuple[str, ...] = ("model",)
    n_replicas: int = 1
    replica_axis: str = "data"


@dataclasses.dataclass(frozen=True)
class ServiceSpec:
    """The whole service, declaratively.  See ``spfresh.open``."""

    index: IndexSpec = IndexSpec()
    serve: ServeSpec = ServeSpec()
    scan: ScanSpec = ScanSpec()
    maintenance: MaintenanceSpec = MaintenanceSpec()
    durability: DurabilitySpec = DurabilitySpec()
    shards: ShardSpec = ShardSpec()

    # ------------------------------------------------------------------
    @property
    def sharded(self) -> bool:
        return self.shards.n_shards > 1

    @property
    def replicated(self) -> bool:
        return self.shards.n_replicas > 1

    def lire_config(self) -> LireConfig:
        """IndexSpec.config with the scan/maintenance overrides folded in —
        the ONE config both backends and every jitted step see."""
        over: dict = {}
        s, m = self.scan, self.maintenance
        for field, value in (
            ("use_pallas_scan", s.use_pallas_scan),
            ("scan_schedule", s.scan_schedule),
            ("scan_page_budget", s.scan_page_budget),
            ("codec", s.codec),
            ("rerank_factor", s.rerank_factor),
            ("jobs_per_round", m.jobs_per_round),
            ("merge_fanout", m.merge_fanout),
            ("reassign_budget", m.reassign_budget),
            ("maintain_policy", m.policy),
            ("maintain_alpha", m.alpha),
            ("maintain_beta", m.beta),
        ):
            if value is not None:
                over[field] = value
        cfg = dataclasses.replace(self.index.config, **over) if over \
            else self.index.config
        cfg.validate()
        return cfg

    def engine_config(self):
        """Compile serve+scan+maintenance into the pipeline's EngineConfig."""
        from repro.serve.engine import EngineConfig

        cfg = self.lire_config()
        sv, sc, mt = self.serve, self.scan, self.maintenance
        return EngineConfig(
            search_k=sv.search_k,
            nprobe=sv.nprobe,
            probe_chunk=sc.probe_chunk,
            use_pallas_scan=sc.use_pallas_scan,
            scan_schedule=sc.scan_schedule,
            max_batch=sv.max_batch,
            min_bucket=sv.min_bucket,
            policy=sv.policy,
            fg_bg_ratio=sv.fg_bg_ratio,
            maintain_budget=(
                mt.maintain_budget
                if mt.maintain_budget is not None
                else cfg.jobs_per_round
            ),
            backlog_threshold=sv.backlog_threshold,
            max_insert_retries=sv.max_insert_retries,
            async_serve=sv.async_serve,
            max_wait_ms=sv.max_wait_ms,
            max_lag=sv.max_lag,
            replica_inflight=sv.replica_inflight,
        )

    def validate(self) -> None:
        self.lire_config()  # folds + validates
        assert self.shards.n_shards >= 1
        assert self.shards.n_replicas >= 1
        assert self.serve.policy in ("ratio", "backlog"), self.serve.policy
        assert self.serve.max_wait_ms >= 0
        assert self.serve.max_lag >= 0
        assert self.serve.replica_inflight >= 1
        assert self.durability.checkpoint_every >= 0
        dur = self.durability
        assert dur.delta_every >= 0 and dur.compact_every >= 0
        assert dur.group_commit >= 0 and dur.group_commit_ms >= 0
        if dur.root is None and (dur.wal_dir is None) != (
                dur.snapshot_dir is None):
            # Half-configured durability would silently run ephemeral.
            raise ValueError(
                "DurabilitySpec needs BOTH wal_dir and snapshot_dir (or "
                "just root); only one of them configures nothing"
            )
        if self.scan.scan_schedule is not None:
            assert self.scan.scan_schedule in ("per_query", "batched")
        if self.scan.codec is not None:
            assert self.scan.codec in ("fp32", "bf16", "int8"), self.scan.codec
        if self.scan.rerank_factor is not None:
            assert self.scan.rerank_factor >= 1

    # ------------------------------------------------------------------
    def with_durability(self, root: str, **kw) -> "ServiceSpec":
        """Convenience: the same service, durably rooted at ``root``."""
        return dataclasses.replace(
            self, durability=dataclasses.replace(
                self.durability, root=root, **kw
            )
        )

    def with_shards(self, n_shards: int, **kw) -> "ServiceSpec":
        """Convenience: the same service over an ``n_shards`` mesh."""
        return dataclasses.replace(
            self, shards=dataclasses.replace(
                self.shards, n_shards=n_shards, **kw
            )
        )

    def with_replicas(self, n_replicas: int, *, max_lag: int | None = None,
                      ) -> "ServiceSpec":
        """Convenience: the same service with ``n_replicas`` read replicas."""
        serve = self.serve if max_lag is None else dataclasses.replace(
            self.serve, max_lag=max_lag
        )
        return dataclasses.replace(
            self,
            serve=serve,
            shards=dataclasses.replace(self.shards, n_replicas=n_replicas),
        )
