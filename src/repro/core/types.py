"""Index state and protocol configuration for SPFresh/LIRE.

Everything is fixed-capacity and functional: ``IndexState`` is a pytree whose
static geometry (capacities, protocol thresholds) lives in a hashable
``LireConfig`` aux field.  A LIRE operation is ``state' = op(state, ...)``
under jit.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.storage.blockpool import BlockPool, make_block_pool
from repro.utils.tree import field, pytree_dataclass

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class LireConfig:
    """Static protocol + geometry parameters (hashable; pytree aux data)."""

    dim: int = 128
    # --- storage geometry ---
    block_size: int = 16            # vectors per block ("SSD block")
    max_blocks_per_posting: int = 8  # MB; posting capacity = BS*MB
    num_blocks: int = 4096           # B_cap
    num_postings_cap: int = 512      # P_cap
    num_vectors_cap: int = 65536     # N_cap (version map size)
    vector_dtype: str = "float32"    # storage dtype for posting payloads
    scan_dtype: str = "float32"      # distance-scan compute dtype (f32 accum)
    # --- tiered posting codec (storage/codec.py) ---
    # "fp32": hot tier stores vector_dtype verbatim (pre-codec behavior).
    # "bf16"/"int8": hot tier stores bf16 / per-posting-quantized int8
    #   (scan bytes ÷2 / ÷4) and a cold exact-fp32 tier serves maintenance
    #   reads and the search rerank.
    codec: str = "fp32"
    # Quantized scans over-fetch rerank_factor×k candidates, then rerank
    # the survivors against the exact tier before the final top-k.  1 =
    # no rerank (exact codecs don't need one).
    rerank_factor: int = 1
    # --- LIRE protocol ---
    split_limit: int = 96            # split when live length exceeds this
    merge_limit: int = 12            # merge when 0 < live length below this
    merge_fanout: int = 4            # nearest postings tried as merge absorbers
    reassign_range: int = 8          # nearby postings scanned after a split (paper: 64)
    reassign_budget: int = 256       # max vectors actually reassigned per pass
    replica_count: int = 4           # max closure replicas per vector (paper avg 5.47, max 8)
    replica_rng: float = 1.15        # replicate while d <= rng^2 * d_min (squared-L2 ratio)
    # --- maintenance batching (the Local Rebuilder round) ---
    # Jobs per `maintenance_round`: the top-K oversized postings are split
    # and the bottom-K undersized merged in ONE fused dispatch, with every
    # job's reassign candidates routed by a single GEMM.  1 degenerates to
    # the sequential `maintenance_step` work shape.
    jobs_per_round: int = 4
    # --- maintenance job selection (drift-aware cost model) ---
    # "size":  top-K longest / bottom-K shortest — the original selection,
    #          kept bit-identical as the parity baseline.
    # "drift": Ada-IVF-style cost-model ranking over the per-posting
    #          telemetry leaves: split priority ~ imbalance ×
    #          (1 + alpha·access_rate) + beta·drift, merge priority ~
    #          len × (1 + alpha·access_rate) ascending.  Eligibility is
    #          unchanged (only oversized postings split, only undersized
    #          merge); with all-zero telemetry the ranking degrades to the
    #          size ordering exactly.
    maintain_policy: str = "size"
    maintain_alpha: float = 1.0      # access-rate weight (drift policy)
    maintain_beta: float = 1.0       # centroid-drift weight (drift policy)
    # --- search ---
    nprobe: int = 8                  # postings probed per query (paper: 64)
    # --- split clustering ---
    kmeans_iters: int = 8
    # --- protocol ablations (benchmarks: SPANN+ / +split / full LIRE) ---
    enable_split: bool = True
    enable_merge: bool = True
    enable_reassign: bool = True
    # --- kernel integration (compiled on a TPU, interpreted elsewhere:
    # repro.kernels.backend decides from the platform) ---
    use_pallas_nav: bool = False
    # Paged Pallas posting scan (search hot path).  False = XLA gather
    # oracle (`bp.parallel_get` + diff²), the default on CPU.  True streams
    # SSD-block-sized pages through the `posting_scan` kernels and emits
    # per-page k-min candidates — the (Q, nprobe·cap, d) gather buffer and
    # the (Q, nprobe·MB·BS) distance matrix are never materialized.
    use_pallas_scan: bool = False
    # "per_query": paper-faithful ParallelGET schedule — every probed page
    #   streamed once per (query, probe); HBM traffic = Q·nprobe·MB pages.
    # "batched": batch-dedup schedule — the micro-batch's probed pages are
    #   deduped and each unique page is streamed ONCE, scored against all
    #   Q queries with one MXU GEMM; traffic divides by the average probe
    #   multiplicity.
    scan_schedule: str = "per_query"
    # Static page cap for the batched schedule's fixed-shape dedup
    # compaction.  Its grid is the most pages a bucket of Q rows can
    # probe, min(Q·nprobe·MB, num_blocks) (lossless); a nonzero cap below
    # that bounds it further, dropping the highest-numbered pages on
    # overflow (counted, see `dedup_pages`).  0 = no cap.
    scan_page_budget: int = 0

    @property
    def posting_capacity(self) -> int:
        return self.block_size * self.max_blocks_per_posting

    def validate(self) -> None:
        assert self.split_limit <= self.posting_capacity, (
            "split_limit must fit in a posting"
        )
        assert self.merge_limit < self.split_limit
        assert self.merge_fanout >= 1
        assert self.jobs_per_round >= 1
        assert 2 * self.jobs_per_round <= self.num_postings_cap, (
            "a round allocates up to 2 pids per split job"
        )
        assert self.replica_count >= 1
        assert self.nprobe >= 1
        assert self.maintain_policy in ("size", "drift"), self.maintain_policy
        assert self.maintain_alpha >= 0.0
        assert self.maintain_beta >= 0.0
        assert self.scan_schedule in ("per_query", "batched"), self.scan_schedule
        assert self.scan_page_budget >= 0
        assert self.codec in ("fp32", "bf16", "int8"), self.codec
        assert self.rerank_factor >= 1


@pytree_dataclass
class LireStats:
    """Cumulative protocol counters (paper §5.2 reports these)."""

    n_inserts: Array        # external insert requests
    n_deletes: Array        # external delete requests
    n_appends: Array        # physical appends (inserts × replicas + reassigns)
    n_append_drops: Array   # appends dropped (posting/pool at capacity)
    n_splits: Array         # split actions executed
    n_gc_writebacks: Array  # split jobs resolved by GC-only write-back
    n_merges: Array         # merge actions executed
    n_reassign_checked: Array  # vectors evaluated by the NPA conditions
    n_reassign_candidates: Array  # vectors passing the necessary conditions
    n_reassigned: Array     # vectors actually reassigned (post NPA re-check)
    n_reassign_overflow: Array  # candidates dropped by reassign_budget

    @staticmethod
    def zeros() -> "LireStats":
        # Distinct buffers per counter: donated update steps (serve pipeline)
        # reject pytrees whose leaves alias the same buffer.
        return LireStats(*(jnp.zeros((), jnp.int32) for _ in range(11)))


@pytree_dataclass
class LireTelemetry:
    """Per-posting maintenance telemetry (Ada-IVF cost-model inputs).

    All three leaves live in ``IndexState`` and are bumped ONLY inside the
    jitted update/maintenance steps, so WAL replay reproduces them
    bit-exactly.  Search probes are the one externally-sourced signal:
    they accumulate host-side in the serving backend and enter the state
    as an explicit operand of the next WAL-logged maintenance dispatch.
    """

    access_count: Array  # (P_cap,) i32 — search probes, folded at dispatch
    update_count: Array  # (P_cap,) i32 — appends landed since (re)creation
    drift_vec: Array     # (P_cap, d) f32 — summed x - centroid[pid] since split

    @staticmethod
    def zeros(cfg: "LireConfig") -> "LireTelemetry":
        p = cfg.num_postings_cap
        return LireTelemetry(
            access_count=jnp.zeros((p,), jnp.int32),
            update_count=jnp.zeros((p,), jnp.int32),
            drift_vec=jnp.zeros((p, cfg.dim), jnp.float32),
        )


@pytree_dataclass
class IndexState:
    cfg: LireConfig = field(static=True)
    pool: BlockPool
    centroids: Array        # (P_cap, d) f32
    centroid_sqn: Array     # (P_cap,) f32 cached ||c||^2
    centroid_valid: Array   # (P_cap,) bool
    versions: Array         # (N_cap,) u8 — 7-bit version + deletion bit
    pid_free_stack: Array   # (P_cap,) i32
    pid_free_top: Array     # () i32
    rng: Array              # PRNG key for split clustering
    step: Array             # () i32 monotonically increasing op counter
    next_vid: Array         # () i32 — local slot allocator (distributed insert)
    stats: LireStats
    # NOTE: keep `telemetry` LAST — snapshots written before it existed are
    # migrated by reconstructing the missing trailing leaves as zeros
    # (storage/snapshot.py).
    telemetry: LireTelemetry

    @property
    def n_postings(self) -> Array:
        return jnp.sum(self.centroid_valid.astype(jnp.int32))


def make_empty_state(cfg: LireConfig, seed: int = 0) -> IndexState:
    cfg.validate()
    dtype = jnp.dtype(cfg.vector_dtype)
    pool = make_block_pool(
        num_blocks=cfg.num_blocks,
        block_size=cfg.block_size,
        dim=cfg.dim,
        num_postings_cap=cfg.num_postings_cap,
        max_blocks_per_posting=cfg.max_blocks_per_posting,
        dtype=dtype,
        codec=cfg.codec,
    )
    p = cfg.num_postings_cap
    return IndexState(
        cfg=cfg,
        pool=pool,
        centroids=jnp.zeros((p, cfg.dim), jnp.float32),
        centroid_sqn=jnp.zeros((p,), jnp.float32),
        centroid_valid=jnp.zeros((p,), bool),
        # +1: reserved scratch slot for disabled scatter rows (see versionmap).
        versions=jnp.zeros((cfg.num_vectors_cap + 1,), jnp.uint8),
        pid_free_stack=jnp.arange(p, dtype=jnp.int32),
        pid_free_top=jnp.asarray(p, jnp.int32),
        rng=jax.random.PRNGKey(seed),
        step=jnp.asarray(0, jnp.int32),
        next_vid=jnp.asarray(0, jnp.int32),
        stats=LireStats.zeros(),
        telemetry=LireTelemetry.zeros(cfg),
    )


def alloc_pid(state: IndexState, enable: Array) -> tuple[IndexState, Array]:
    """Pop a posting id from the free stack (-1 on exhaustion/no-op)."""
    has = enable & (state.pid_free_top > 0)
    top = jnp.maximum(state.pid_free_top - 1, 0)
    pid = jnp.where(has, state.pid_free_stack[top], -1)
    state = state.replace(
        pid_free_top=jnp.where(has, top, state.pid_free_top)
    )
    return state, pid


def free_pid(state: IndexState, pid: Array, enable: Array) -> IndexState:
    do = enable & (pid >= 0)
    safe = jnp.maximum(pid, 0)
    stack = jnp.where(
        do,
        state.pid_free_stack.at[state.pid_free_top].set(pid.astype(jnp.int32)),
        state.pid_free_stack,
    )
    valid = jnp.where(
        do, state.centroid_valid.at[safe].set(False),
        state.centroid_valid,
    )
    # Freed pids come back off the stack with zero telemetry — the leaves
    # always describe the CURRENT posting living at a pid.
    tel = state.telemetry
    tel = tel.replace(
        access_count=jnp.where(
            do, tel.access_count.at[safe].set(0), tel.access_count
        ),
        update_count=jnp.where(
            do, tel.update_count.at[safe].set(0), tel.update_count
        ),
        drift_vec=jnp.where(
            do, tel.drift_vec.at[safe].set(0.0), tel.drift_vec
        ),
    )
    return state.replace(
        pid_free_stack=stack,
        pid_free_top=jnp.where(do, state.pid_free_top + 1, state.pid_free_top),
        centroid_valid=valid,
        telemetry=tel,
    )


def alloc_pids(state: IndexState, enable: Array) -> tuple[IndexState, Array]:
    """Batched pid alloc: pop one id per enabled row, in ONE gather.

    Pops follow the sequential `alloc_pid` LIFO order (row with the i-th
    True gets ``stack[top - i]``); rows past stack exhaustion get ``-1``.
    Returns ``(state, pids (k,))``.
    """
    cnt = jnp.cumsum(enable.astype(jnp.int32))  # inclusive
    pos = state.pid_free_top - cnt
    ok = enable & (pos >= 0)
    pids = jnp.where(ok, state.pid_free_stack[jnp.maximum(pos, 0)], -1)
    return (
        state.replace(pid_free_top=state.pid_free_top - jnp.sum(ok)),
        pids.astype(jnp.int32),
    )


def free_pids(state: IndexState, pids: Array, enable: Array) -> IndexState:
    """Batched `free_pid`: push ``k`` (distinct) ids back in ONE scatter and
    invalidate their centroids."""
    do = enable & (pids >= 0)
    pos = state.pid_free_top + jnp.cumsum(do.astype(jnp.int32)) - 1
    cap = state.pid_free_stack.shape[0]
    stack = state.pid_free_stack.at[jnp.where(do, pos, cap)].set(
        pids.astype(jnp.int32), mode="drop"
    )
    tgt = jnp.where(do, jnp.maximum(pids, 0), cap)
    valid = state.centroid_valid.at[tgt].set(False, mode="drop")
    tel = state.telemetry
    tel = tel.replace(
        access_count=tel.access_count.at[tgt].set(0, mode="drop"),
        update_count=tel.update_count.at[tgt].set(0, mode="drop"),
        drift_vec=tel.drift_vec.at[tgt].set(0.0, mode="drop"),
    )
    return state.replace(
        pid_free_stack=stack,
        pid_free_top=state.pid_free_top + jnp.sum(do),
        centroid_valid=valid,
        telemetry=tel,
    )


def set_centroids(
    state: IndexState, pids: Array, centroids: Array, enable: Array
) -> IndexState:
    """Batched `set_centroid`: ``k`` (distinct) centroid writes in ONE
    scatter.  ``centroids (k, d)``; disabled rows are dropped."""
    do = enable & (pids >= 0)
    cap = state.centroids.shape[0]
    tgt = jnp.where(do, jnp.maximum(pids, 0), cap)
    c = centroids.astype(jnp.float32)
    return state.replace(
        centroids=state.centroids.at[tgt].set(c, mode="drop"),
        centroid_sqn=state.centroid_sqn.at[tgt].set(
            jnp.sum(c * c, axis=-1), mode="drop"
        ),
        centroid_valid=state.centroid_valid.at[tgt].set(True, mode="drop"),
    )


def set_centroid(
    state: IndexState, pid: Array, centroid: Array, enable: Array
) -> IndexState:
    safe = jnp.maximum(pid, 0)
    do = enable & (pid >= 0)
    c = centroid.astype(jnp.float32)
    centroids = jnp.where(do, state.centroids.at[safe].set(c), state.centroids)
    sqn = jnp.where(
        do, state.centroid_sqn.at[safe].set(jnp.sum(c * c)), state.centroid_sqn
    )
    valid = jnp.where(
        do, state.centroid_valid.at[safe].set(True), state.centroid_valid
    )
    return state.replace(
        centroids=centroids, centroid_sqn=sqn, centroid_valid=valid
    )


def bump_stat(stats: LireStats, name: str, amount) -> LireStats:
    return stats.replace(
        **{name: getattr(stats, name) + jnp.asarray(amount, jnp.int32)}
    )
