"""spflint analysis spec: what the passes check, declared as data.

The passes themselves are generic AST machinery (replay.py / locks.py /
vmem.py); everything repo-specific — which jit-step builders are replay
roots, where the stamp tuples live, the VMEM reference serving shape —
is pinned HERE so the fixture tests can aim the same passes at seeded
violation trees with a different spec.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ReplaySpec:
    """Replay-determinism pass inputs.

    ``roots`` are the functions whose transitive callees constitute the
    WAL-replayed dispatch surface: every config field read reachable from
    them must be classified (stamped replay-critical, or explicitly
    exempt with a reason) and no wall-clock / unseeded-RNG / set-order
    dependence may be reachable.
    """

    roots: tuple[str, ...]        # "module:qualname" entries
    config_class: str             # "module:Class" (the frozen config)
    critical_stamp: str           # "module:NAME" tuple of stamped fields
    exempt_stamp: str             # "module:NAME" tuple of exempt fields


@dataclasses.dataclass(frozen=True)
class LockSpec:
    """Lock-discipline pass inputs: modules scanned for classes that
    declare a ``FIELD_OWNERSHIP`` map (the pass is opt-in per class)."""

    module_prefixes: tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class VmemSpec:
    """Pallas resource pass inputs.

    ``bindings`` is the reference serving shape every ``pl.pallas_call``
    site is evaluated at — symbols the kernel wrappers take from operand
    shapes or parameters.  ``dtype_overrides`` maps
    ``(module, wrapper_qualname) -> {in_spec index: dtype}`` for
    operands that are not the default float32 (the int8 code pages).
    """

    module_prefixes: tuple[str, ...]
    budget_bytes: int
    bindings: dict
    dtype_overrides: dict


@dataclasses.dataclass(frozen=True)
class AnalysisSpec:
    replay: ReplaySpec
    locks: LockSpec
    vmem: VmemSpec


# ---------------------------------------------------------------------------
# The repo's own spec
# ---------------------------------------------------------------------------

# Reference serving shape for the VMEM table: the per-shard production
# geometry of spfresh-1b (configs/spfresh.py CONFIG_PAGED: d=100 int8
# pages of 32 vectors, nprobe=64 over 4-page postings → nb = 256 pages
# per query, k=10 candidates per page, a 1024-query search batch, 65,536
# centroids per shard) and the l2_topk tile defaults (block_q=128,
# block_p=512).  One ``k`` serves every kernel: the scan's per-page k;
# navigation's per-tile k is nprobe, which the estimate understates.
VMEM_BINDINGS = {
    "dim": 100,        # vector dimension
    "bs": 32,          # block_size: vectors per SSD page
    "k": 10,           # per-page / per-tile candidates kept
    "q_n": 1024,       # queries per micro-batch dispatch
    "nb": 256,         # pages per query (nprobe * max_blocks_per_posting)
    "block_q": 128,    # l2_topk query tile
    "block_p": 512,    # l2_topk centroid tile
    "p_n": 65536,      # centroids per shard (l2_topk input rows)
}

DEFAULT_SPEC = AnalysisSpec(
    replay=ReplaySpec(
        roots=(
            # single-host jit-step builders (the WAL dispatch surface)
            "repro.core.index:insert_step",
            "repro.core.index:delete_step",
            "repro.core.index:fused_maintenance_step",
            "repro.core.index:fused_maintenance_round",
            # sharded builders (shard_map'd twins of the same dispatches)
            "repro.distributed.sharded_index:make_insert_step",
            "repro.distributed.sharded_index:make_delete_step",
            "repro.distributed.sharded_index:make_maintenance_step",
            # template + codec selection: recovery rebuilds the state
            # pytree from the config before replaying the WAL onto it
            "repro.core.types:make_empty_state",
        ),
        config_class="repro.core.types:LireConfig",
        critical_stamp="repro.storage.durability:REPLAY_CRITICAL_FIELDS",
        exempt_stamp="repro.storage.durability:REPLAY_EXEMPT_FIELDS",
    ),
    locks=LockSpec(module_prefixes=("repro.serve",)),
    vmem=VmemSpec(
        module_prefixes=("repro.kernels",),
        budget_bytes=16 * 1024 * 1024,   # VMEM per TensorCore (~16 MiB)
        bindings=VMEM_BINDINGS,
        dtype_overrides={
            # int8 pages (the production pool stores int8 payloads under
            # every codec): in_specs index 1 is the block-pool operand
            ("repro.kernels.posting_scan.kernel", "_scan_per_query_rows"):
                {1: "int8"},
            ("repro.kernels.posting_scan.kernel", "scan_batched"):
                {1: "int8"},
            ("repro.kernels.posting_scan.kernel",
             "_scan_per_query_topk_rows"): {1: "int8"},
            ("repro.kernels.posting_scan.kernel",
             "_scan_per_query_topk_q8_rows"): {1: "int8"},
            ("repro.kernels.posting_scan.kernel", "scan_batched_topk"):
                {1: "int8"},
            ("repro.kernels.posting_scan.kernel", "scan_batched_topk_q8"):
                {1: "int8"},
        },
    ),
)
