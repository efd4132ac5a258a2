"""spfresh-1b — the paper's own architecture at billion scale.

Document-sharded SPFresh: one LIRE shard per device (256 on the single-pod
16×16 mesh, 512 on the 2×16×16 multi-pod mesh).  Per-shard geometry below
holds ~2M live vectors (≈8M replica slots): 256 shards ≈ 0.5B, 512 shards
≈ 1.1B vectors — the paper's SPACEV1B/SIFT1B regime with int8 payloads.

Cells (serving steps, the paper's §5 workloads):
  * serve_search — Q=1024 queries, k=10, nprobe=64 (paper's search setting)
  * serve_update — B=4096 inserts routed + appended (Updater)
  * maintain     — one Local-Rebuilder round on every shard in parallel
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.common import Cell, _sds
from repro.core.types import LireConfig, make_empty_state
from repro.distributed import sharded_index as D

# Per-shard geometry (per device).
CONFIG = LireConfig(
    dim=100,                      # SPACEV byte vectors
    block_size=32,
    # §Perf iter 1: capacity 256→160 (MB 8→5).  The scan gathers FULL
    # posting buffers; steady-state live length sits between merge_limit
    # and split_limit, so capacity slack is pure HBM waste.  160 keeps
    # split_limit+GC headroom while cutting scan traffic 1.6×.
    max_blocks_per_posting=4,     # posting capacity 128
    num_blocks=262_144,           # 838 MB int8 payload / device
    num_postings_cap=65_536,
    num_vectors_cap=4_194_304,    # 4M handles / shard
    vector_dtype="int8",
    scan_dtype="bfloat16",        # §Perf iter 2: halve upcast traffic in the scan
    split_limit=96,
    merge_limit=12,
    merge_fanout=4,
    reassign_range=64,            # paper default (Fig. 11)
    reassign_budget=256,
    replica_count=4,
    replica_rng=1.15,
    nprobe=64,                    # paper: search nearest 64 postings
    # Batched Local-Rebuilder rounds: 8 splits + 8 merges per shard per
    # round, one fused reassign GEMM (1% daily churn on 2M live vectors
    # per shard ≈ a handful of oversized postings per serving slot).
    jobs_per_round=8,
    # Drift-aware job selection: at 8 jobs over 65k postings the round
    # budget is scarce, so rank by access rate × imbalance + centroid
    # drift instead of size alone (BENCH_scenarios.json shift cell).
    maintain_policy="drift",
    maintain_alpha=4.0,
    maintain_beta=1.0,
)

SMOKE = LireConfig(
    dim=16, block_size=8, max_blocks_per_posting=8, num_blocks=1024,
    num_postings_cap=128, num_vectors_cap=4096, split_limit=48,
    merge_limit=6, merge_fanout=4, reassign_range=8, reassign_budget=128,
    replica_count=2, nprobe=8, jobs_per_round=4,
)

SEARCH_Q = 1024
UPDATE_B = 4096
# probe_chunk=0: the probe-chunk lax.scan would be counted once by XLA's
# cost analysis; unchunked gives exact FLOP/byte counts for the roofline
# (the Pallas posting_scan kernel bounds real VMEM use on hardware).
PROBE_CHUNK = 0

# Paged-scan production path (serve_search_paged): the batch-dedup Pallas
# schedule with a static page cap.  A micro-batch of Q queries probes at
# most Q·nprobe·MB pages, which sizes its kernel grid; 32768 (=
# num_blocks/8) caps it, and pages past the cap are dropped (counted by
# dedup_pages):
# at Q=1024 queries spread over a 1M-vector shard that is most of them.
# The paged service spec therefore caps its micro-batch at
# budget / (nprobe·MB) = 128 queries, where no probe can be dropped.
# The kernels compile on a TPU and run the Pallas interpreter on the CPU
# (repro.kernels.backend), so the same config serves both.
CONFIG_PAGED = dataclasses.replace(
    CONFIG,
    use_pallas_scan=True,
    scan_schedule="batched",
    scan_page_budget=32_768,
)


# ---------------------------------------------------------------------------
# Service specs — the deployable description of this architecture for
# `spfresh.open` (the serving knobs that used to be hand-threaded through
# EngineConfig/backend ctors live here, next to the geometry they tune).
# ---------------------------------------------------------------------------

def service_spec(*, paged: bool = True, smoke: bool = False,
                 n_shards: int = 1, durable_root: str | None = None,
                 n_replicas: int = 1, max_lag: int = 64):
    """The production ServiceSpec for spfresh-1b (or its smoke twin).

    ``spfresh.open(service_spec(smoke=True), vectors=...)`` stands up a
    runnable miniature of the billion-scale deployment; on real hardware
    pass ``n_shards=256`` (single-pod) and a durable root per node.
    ``n_replicas > 1`` adds data-axis read replicas fed by the async WAL
    replication stream (distributed/replication.py); ``max_lag`` is the
    freshness bound in WAL seqnos before a search falls back to the
    primary.
    """
    import spfresh

    base = SMOKE if smoke else (CONFIG_PAGED if paged else CONFIG)
    max_batch = SEARCH_Q
    if base.use_pallas_scan and base.scan_page_budget:
        max_batch = min(max_batch, base.scan_page_budget // (
            base.nprobe * base.max_blocks_per_posting))
    return spfresh.ServiceSpec(
        index=spfresh.IndexSpec(config=base),
        serve=spfresh.ServeSpec(
            search_k=10, nprobe=base.nprobe, max_batch=max_batch,
            max_lag=max_lag,
        ),
        scan=spfresh.ScanSpec(probe_chunk=PROBE_CHUNK),
        maintenance=spfresh.MaintenanceSpec(
            jobs_per_round=base.jobs_per_round,
            policy=base.maintain_policy,
            alpha=base.maintain_alpha,
            beta=base.maintain_beta,
        ),
        durability=spfresh.DurabilitySpec(root=durable_root),
        shards=spfresh.ShardSpec(n_shards=n_shards, n_replicas=n_replicas),
    )


def _shard_axes(multi_pod: bool):
    return ("pod", "data", "model") if multi_pod else ("data", "model")


def _n_shards(multi_pod: bool):
    return 512 if multi_pod else 256


def _stacked_state_specs(n_shards: int):
    abstract = jax.eval_shape(lambda: make_empty_state(CONFIG))
    return jax.tree_util.tree_map(
        lambda x: _sds((n_shards, *x.shape), x.dtype), abstract
    )


# two-level router geometry (§Perf Cell A iter 4): 512 groups of ≤256
# centroids per shard; queries probe the 32 nearest groups
N_GROUPS = 512
GROUP_CAP = 256
GPROBE = 32


def _make_mesh_step(shape: str):
    def make(mesh, multi_pod: bool):
        axes = _shard_axes(multi_pod)
        n = _n_shards(multi_pod)
        state_specs = _stacked_state_specs(n)
        if shape == "serve_search":
            fn = D.make_search_step(
                mesh, CONFIG, k=10, shard_axes=axes, probe_chunk=PROBE_CHUNK
            )
            args = (
                state_specs,
                _sds((SEARCH_Q, CONFIG.dim), jnp.float32),
                _sds((n,), jnp.bool_),
            )
            return fn, args
        if shape == "serve_search_paged":
            fn = D.make_search_step(
                mesh, CONFIG_PAGED, k=10, shard_axes=axes,
                probe_chunk=PROBE_CHUNK, use_pallas_scan=True,
                scan_schedule="batched",
            )
            paged_specs = jax.tree_util.tree_map(
                lambda x: _sds((n, *x.shape), x.dtype),
                jax.eval_shape(lambda: make_empty_state(CONFIG_PAGED)),
            )
            args = (
                paged_specs,
                _sds((SEARCH_Q, CONFIG.dim), jnp.float32),
                _sds((n,), jnp.bool_),
            )
            return fn, args
        if shape == "serve_search_grouped":
            from repro.core.grouping import GroupIndex

            fn = D.make_search_step(
                mesh, CONFIG, k=10, shard_axes=axes,
                probe_chunk=PROBE_CHUNK, gprobe=GPROBE,
            )
            gi = GroupIndex(
                group_centroids=_sds((n, N_GROUPS, CONFIG.dim), jnp.float32),
                group_sqn=_sds((n, N_GROUPS), jnp.float32),
                members=_sds((n, N_GROUPS, GROUP_CAP), jnp.int32),
                member_valid=_sds((n, N_GROUPS, GROUP_CAP), jnp.bool_),
            )
            args = (
                state_specs,
                _sds((SEARCH_Q, CONFIG.dim), jnp.float32),
                _sds((n,), jnp.bool_),
                gi,
            )
            return fn, args
        if shape == "serve_update":
            fn = D.make_insert_step(mesh, CONFIG, shard_axes=axes)
            args = (state_specs, _sds((UPDATE_B, CONFIG.dim), jnp.float32))
            return fn, args
        if shape == "maintain":
            fn = D.make_maintenance_round(
                mesh, CONFIG, shard_axes=axes,
                jobs_per_round=CONFIG.jobs_per_round,
            )
            return fn, (state_specs,)
        raise KeyError(shape)
    return make


def cells() -> list[Cell]:
    out = []
    for shape in ("serve_search", "serve_search_paged",
                  "serve_search_grouped", "serve_update", "maintain"):
        c = Cell(
            arch="spfresh-1b", shape=shape, family="index",
            kind="serve", model_cfg=CONFIG, smoke_cfg=SMOKE,
            step_fn=None, input_specs=None, in_shardings=None,
            make_smoke_inputs=None,
        )
        c.make_mesh_step = _make_mesh_step(shape)
        out.append(c)
    return out
