"""Two-level centroid routing — the arithmetic-intensity-optimized
replacement for SPANN's SPTAG navigation graph (beyond-paper opt #1).

The flat navigator computes a (Q × P) distance GEMM over every posting
centroid.  At billion scale (P ≈ 65k/shard) that is ~90% of the search
FLOPs.  Two-level routing clusters the centroids into G balanced groups;
a query first scores the G group centroids, then scores only the members
of its ``gprobe`` nearest groups:

    FLOPs: Q·G·d + Q·gprobe·γ·d   vs   Q·P·d      (γ = group capacity)
    e.g. P=65536, G=256, γ=512, gprobe=8 → ~12× fewer navigation FLOPs.

Freshness: the group index is a *derived* structure rebuilt by the host at
the same cadence the paper updates its in-memory SPTAG index ("when the
background split and merge jobs are complete") — splits between refreshes
leave new centroids unrouted, which degrades recall gracefully until the
next refresh (measured in tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.clustering import balanced_kmeans
from repro.core.distance import MASK_DISTANCE, masked_topk, pairwise_sql2
from repro.core.types import IndexState
from repro.utils.tree import field, pytree_dataclass

Array = jax.Array


@pytree_dataclass
class GroupIndex:
    group_centroids: Array   # (G, d) f32
    group_sqn: Array         # (G,) f32
    members: Array           # (G, gamma) i32 posting ids, -1 empty
    member_valid: Array      # (G, gamma) bool


def build_group_index(
    state: IndexState, *, n_groups: int, capacity: int, seed: int = 0
) -> GroupIndex:
    """Cluster the valid posting centroids into ``n_groups`` balanced
    groups (host-driven; rebuilt after maintenance rounds)."""
    cen, assign = balanced_kmeans(
        jax.random.PRNGKey(seed),
        state.centroids,
        state.centroid_valid,
        k=n_groups,
        iters=10,
        balance_weight=2.0,
    )
    import numpy as np

    assign_np = np.asarray(assign)
    members = np.full((n_groups, capacity), -1, np.int32)
    counts = np.zeros(n_groups, np.int64)
    dropped = 0
    for pid in np.where(np.asarray(state.centroid_valid))[0]:
        g = assign_np[pid]
        if g < 0:
            continue
        if counts[g] < capacity:
            members[g, counts[g]] = pid
            counts[g] += 1
        else:
            # overflow: place in the least-full group (rare w/ balance)
            g2 = int(np.argmin(counts))
            if counts[g2] < capacity:
                members[g2, counts[g2]] = pid
                counts[g2] += 1
            else:
                dropped += 1
    assert dropped == 0, f"group capacity too small: {dropped} dropped"
    gm = jnp.asarray(members)
    cen = cen.astype(jnp.float32)
    return GroupIndex(
        group_centroids=cen,
        group_sqn=jnp.sum(cen * cen, axis=-1),
        members=gm,
        member_valid=gm >= 0,
    )


@functools.partial(jax.jit, static_argnames=("nprobe", "gprobe"))
def navigate_grouped(
    state: IndexState,
    gidx: GroupIndex,
    queries: Array,
    *,
    nprobe: int,
    gprobe: int,
) -> tuple[Array, Array]:
    """Two-level nearest-``nprobe`` postings.  Same interface as
    ``lire.navigate``; exact when gprobe == n_groups."""
    q = queries.shape[0]
    gamma = gidx.members.shape[1]

    # level 1: route to gprobe nearest groups
    dg = pairwise_sql2(queries, gidx.group_centroids, gidx.group_sqn)
    any_member = jnp.any(gidx.member_valid, axis=1)
    _, top_g = masked_topk(dg, any_member[None, :], gprobe)  # (Q, gprobe)

    # level 2: exact distances to the members of those groups
    cand = gidx.members[jnp.maximum(top_g, 0)]        # (Q, gprobe, gamma)
    cand_valid = gidx.member_valid[jnp.maximum(top_g, 0)] & (top_g >= 0)[..., None]
    cand = cand.reshape(q, gprobe * gamma)
    cand_valid = cand_valid.reshape(q, gprobe * gamma)
    safe = jnp.maximum(cand, 0)
    c = state.centroids[safe]                         # (Q, gprobe*gamma, d)
    qf = queries.astype(jnp.float32)
    diff = qf[:, None, :] - c.astype(jnp.float32)
    d = jnp.sum(diff * diff, axis=-1)
    live = cand_valid & state.centroid_valid[safe]
    d = jnp.where(live, d, MASK_DISTANCE)
    top_d, sel = jax.lax.top_k(-d, nprobe)
    top_d = -top_d
    pids = jnp.take_along_axis(cand, sel, axis=1)
    pids = jnp.where(top_d < MASK_DISTANCE / 2, pids, -1)
    return top_d, pids


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "nprobe", "gprobe", "probe_chunk", "use_pallas_scan",
        "scan_schedule",
    ),
)
def search_grouped(
    state: IndexState,
    gidx: GroupIndex,
    queries: Array,
    *,
    k: int,
    nprobe: int | None = None,
    gprobe: int = 8,
    probe_chunk: int = 0,
    use_pallas_scan: bool | None = None,
    scan_schedule: str | None = None,
) -> tuple[Array, Array]:
    """lire.search with two-level navigation.  The scan + reduce is the
    shared ``lire.scan_and_reduce`` data path, so the Pallas paged scan,
    the batch-dedup schedule, and probe chunking all apply here too."""
    from repro.core import lire

    cfg = state.cfg
    nprobe = nprobe or cfg.nprobe
    nav_d, pids = navigate_grouped(
        state, gidx, queries, nprobe=nprobe, gprobe=gprobe
    )
    probe_valid = nav_d < MASK_DISTANCE / 2
    d, v, _ = lire.scan_and_reduce(
        state, queries, pids, probe_valid,
        k=k, probe_chunk=probe_chunk,
        use_pallas_scan=use_pallas_scan, scan_schedule=scan_schedule,
    )
    return d, v
