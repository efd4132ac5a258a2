"""LIRE protocol operations — paper §3 + §4.2.

External interface: :func:`insert_batch`, :func:`delete_batch`,
:func:`search`.  Internal (Local Rebuilder): :func:`split_posting`,
:func:`merge_posting`, :func:`maintenance_step`, and the batched
:func:`maintenance_round` (K split + K merge jobs with one fused
reassignment pass — the update-path analogue of the batched search scan).

Every op is a jittable, fixed-shape functional state transition.  Branchy
protocol logic is expressed with ``enable`` masks threaded through the
storage ops, so a maintenance step is constant work regardless of whether a
job fires (the TPU idiom for the paper's background job queue).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import npa
from repro.core.clustering import balanced_two_means
from repro.core.distance import MASK_DISTANCE, masked_topk, pairwise_sql2, sql2
from repro.core.types import (
    IndexState,
    LireStats,
    alloc_pids,
    bump_stat,
    free_pids,
    set_centroids,
)
from repro.kernels.posting_scan import ops as scan_ops
from repro.storage import blockpool as bp
from repro.storage import versionmap as vm

Array = jax.Array


# ---------------------------------------------------------------------------
# Centroid navigation (the SPTAG replacement: dense GEMM + top-k)
# ---------------------------------------------------------------------------

def navigate(state: IndexState, queries: Array, nprobe: int) -> tuple[Array, Array]:
    """Nearest-``nprobe`` valid posting centroids for each query.

    Returns ``(dists (Q, nprobe), pids (Q, nprobe))``; invalid slots have
    MASK_DISTANCE.  With ``cfg.use_pallas_nav`` the fused Pallas ``l2_topk``
    kernel runs (TPU target; interpret mode on CPU); the pure-XLA GEMM +
    masked top-k below is the oracle and the default CPU path.
    """
    if state.cfg.use_pallas_nav:
        from repro.kernels.l2_topk.ops import l2_topk

        d, idx = l2_topk(
            queries, state.centroids, state.centroid_valid, k=nprobe
        )
        d = jnp.where(idx >= 0, d, MASK_DISTANCE)
        return d, idx
    d = pairwise_sql2(queries, state.centroids, state.centroid_sqn)
    return masked_topk(d, state.centroid_valid[None, :], nprobe)


def route(
    state: IndexState, vecs: Array, r: int
) -> tuple[Array, Array, Array]:
    """Insert/reassign routing: top-``r`` centroids + closure-replica mask.

    A vector is replicated into posting ``i`` iff
    ``d_i <= replica_rng^2 * d_min`` (SPANN closure rule, squared-L2 form).
    Returns ``(pids (B, r), dists (B, r), replica_ok (B, r))``.
    """
    dists, pids = navigate(state, vecs, r)
    dmin = dists[:, :1]
    factor = jnp.float32(state.cfg.replica_rng) ** 2
    replica_ok = (dists <= factor * dmin) & (dists < MASK_DISTANCE / 2)
    return pids, dists, replica_ok


# ---------------------------------------------------------------------------
# Per-posting telemetry (Ada-IVF cost-model inputs, bumped in jitted steps)
# ---------------------------------------------------------------------------

def _bump_append_telemetry(
    state: IndexState, pids: Array, vecs: Array, landed: Array
):
    """Update/drift accounting for a batch of physical appends (insert
    replicas, reassign re-appends, merge moves): every landed row bumps its
    posting's ``update_count`` and accumulates its displacement from the
    CURRENT centroid into ``drift_vec``.  Runs inside the jitted update
    steps, so WAL replay reproduces the leaves bit-exactly."""
    tel = state.telemetry
    cap = state.cfg.num_postings_cap
    safe = jnp.maximum(pids, 0)
    tgt = jnp.where(landed, safe, cap)
    disp = vecs.astype(jnp.float32) - state.centroids[safe]
    disp = jnp.where(landed[:, None], disp, 0.0)
    return tel.replace(
        update_count=tel.update_count.at[tgt].add(1, mode="drop"),
        drift_vec=tel.drift_vec.at[tgt].add(disp, mode="drop"),
    )


def probe_histogram(cfg, pids: Array, probe_valid: Array) -> Array:
    """Per-posting probe counts for one search micro-batch — the access
    signal of the drift-aware maintenance policy.  Searches are NOT
    WAL-logged, so this histogram never touches ``IndexState`` here: the
    serving backend accumulates it host-side and folds it in as an operand
    of the next WAL-logged maintenance dispatch (replay stays bit-exact)."""
    cap = cfg.num_postings_cap
    tgt = jnp.where(probe_valid, pids, cap).reshape(-1)
    return jnp.zeros((cap,), jnp.int32).at[tgt].add(1, mode="drop")


# ---------------------------------------------------------------------------
# External interface: Insert / Delete (the foreground Updater, §4.1)
# ---------------------------------------------------------------------------

@jax.jit
def insert_batch(
    state: IndexState, vecs: Array, vids: Array, valid: Array
) -> tuple[IndexState, Array, Array]:
    """Foreground insert: route to nearest posting(s), append at tail.

    O(1) per append (tail-block write) — splits are *not* done here; the
    background rebuilder discovers oversized postings by length scan.

    Returns ``(state, landed (B,), routes (B, R))`` — ``landed`` is False
    when even the *primary* (nearest-posting) append failed because the
    posting is at hard capacity; ``routes`` are each row's routed
    postings, nearest (the primary) first (-1 for a disabled row).  The
    host Updater applies backpressure: a maintenance round told to split
    the refused rows' primaries first (``maintenance_round``'s
    ``force``), then a retry.  This is the feed-forward pipeline of paper
    §4.2 with explicit backpressure instead of threads.
    """
    cfg = state.cfg

    # (Re)activate the id: clear deletion bit, keep version counter.
    # Disabled rows scatter to the scratch slot (duplicate-index hazard).
    idx = vm._targets(state.versions, vids, valid)
    cur = state.versions[idx]
    cleared = cur & vm.VERSION_MASK
    versions = state.versions.at[idx].set(cleared)
    state = state.replace(versions=versions)

    pids, _, replica_ok = route(state, vecs, cfg.replica_count)
    enable = valid[:, None] & replica_ok  # (B, R)

    flat_pids = pids.reshape(-1)
    flat_enable = enable.reshape(-1)
    flat_vecs = jnp.repeat(vecs, cfg.replica_count, axis=0)
    flat_vids = jnp.repeat(vids, cfg.replica_count)
    flat_vers = jnp.repeat(cleared, cfg.replica_count)

    pool, oks = bp.append_batch(
        state.pool,
        jnp.maximum(flat_pids, 0),
        flat_vecs,
        flat_vids,
        flat_vers,
        flat_enable & (flat_pids >= 0),
    )
    oks2 = oks.reshape(-1, cfg.replica_count)
    landed = oks2[:, 0] | ~valid  # primary append succeeded (or not requested)
    telemetry = _bump_append_telemetry(state, flat_pids, flat_vecs, oks)
    stats = state.stats
    stats = bump_stat(stats, "n_inserts", jnp.sum(valid))
    stats = bump_stat(stats, "n_appends", jnp.sum(oks))
    stats = bump_stat(
        stats, "n_append_drops", jnp.sum(flat_enable & (flat_pids >= 0)) - jnp.sum(oks)
    )
    # all R routes, not only the primary column: a second slice of the
    # routing top-k's indices keeps the TPU compiler from lowering it to
    # its TopK op (it sorts all P_cap centroids a row instead)
    routes = jnp.where(valid[:, None], pids, -1)
    return state.replace(
        pool=pool, stats=stats, telemetry=telemetry, step=state.step + 1
    ), landed, routes


@jax.jit
def delete_batch(state: IndexState, vids: Array, valid: Array) -> IndexState:
    """Tombstone delete (paper: one thread suffices — it's a bit set)."""
    versions = vm.mark_deleted(state.versions, jnp.maximum(vids, 0), valid)
    stats = bump_stat(state.stats, "n_deletes", jnp.sum(valid))
    return state.replace(versions=versions, stats=stats, step=state.step + 1)


# ---------------------------------------------------------------------------
# Search (the SPANN searcher over versioned postings)
# ---------------------------------------------------------------------------

def _dedup_topk_1d_ref(
    dists: Array, vids: Array, live: Array, k: int
) -> tuple[Array, Array]:
    """Reference dedup-top-k (the original reduce, kept as the oracle for
    tests and the before/after benchmark).

    Sort by (vid primary, dist secondary); keep first occurrence of each vid;
    then masked top-k.  ``jnp.lexsort`` is two full O(n log n) sort passes
    over the candidate array — the hottest reduce in search.

    Caveat (fixed by the replacement): a vid whose *minimum-distance*
    occurrence is dead (stale replica closer than the live one) is dropped
    entirely here; callers must pre-mask dead distances to MASK_DISTANCE
    for live-min semantics (the chunked scan path always did).
    """
    order = jnp.lexsort((dists, vids))
    sv = vids[order]
    sl = live[order]
    sd = dists[order]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), sv[1:] != sv[:-1]]
    )
    keep = first & sl
    top_d, sel = masked_topk(sd, keep, k)
    out_vids = jnp.where(top_d < MASK_DISTANCE / 2, sv[sel], -1)
    return top_d, out_vids


def _dedup_prefilter(cfg, k: int, n: int) -> int:
    """Static candidate cap for the dedup reduce: the k-th distinct vid must
    sit within the first ``k * max_live_replicas`` distance-sorted entries.
    ``2 * replica_count`` covers the re-insert-live-id case (old replicas of
    the same version stay live next to the fresh ones)."""
    return max(k, min(n, max(4 * k, 2 * k * cfg.replica_count)))


def _dedup_topk_1d_full(
    dists: Array, vids: Array, live: Array, k: int, prefilter: int
) -> tuple[Array, Array, Array]:
    """Top-k smallest with duplicate-vid suppression (replicas!).

    Replaces the lexsort reduce (see ``_dedup_topk_1d_ref``): one
    ``top_k`` prefilter to ``prefilter`` candidates (distance-sorted, ties
    by index — so within the prefix, an entry's duplicates-with-smaller-
    distance all precede it), then an O(prefilter²) segment-min mask picks
    each vid's first occurrence, then the final masked top-k runs on the
    tiny prefix.  A packed ``(vid << shift | rank)`` single-key sort needs
    64-bit keys (vid caps exceed 2^21), which x64-disabled jax doesn't
    have — the top_k prefilter is strictly cheaper anyway: one partial
    selection instead of two full sorts over n.

    Exact vs the reference whenever each vid has ≤ prefilter/k live
    replicas (callers size ``prefilter`` via ``_dedup_prefilter``); only
    exact cross-vid distance ties can reorder equal-distance results.

    Returns ``(top_d (k,), out_vids (k,), orig_idx (k,))`` — ``orig_idx``
    is each winner's index into the input candidate array (-1 for masked
    rows), which the rerank uses to recover candidate pool positions.
    """
    n = dists.shape[0]
    m = min(max(prefilter, k), n)
    d = jnp.where(live, dists, MASK_DISTANCE)
    neg, sel = jax.lax.top_k(-d, m)
    sd = -neg
    sv = vids[sel]
    idx = jnp.arange(m)
    earlier_dup = (sv[:, None] == sv[None, :]) & (idx[:, None] > idx[None, :])
    keep = ~jnp.any(earlier_dup, axis=1) & (sd < MASK_DISTANCE / 2)
    top_d, s2 = masked_topk(sd, keep, k)
    ok = top_d < MASK_DISTANCE / 2
    out_vids = jnp.where(ok, sv[s2], -1)
    orig_idx = jnp.where(ok, sel[s2], -1)
    return top_d, out_vids, orig_idx


def _dedup_topk_1d(
    dists: Array, vids: Array, live: Array, k: int, prefilter: int
) -> tuple[Array, Array]:
    """`_dedup_topk_1d_full` without the candidate-index output."""
    top_d, out_vids, _ = _dedup_topk_1d_full(dists, vids, live, k, prefilter)
    return top_d, out_vids


def _page_table(
    state: IndexState, pids: Array, probe_valid: Array
) -> Array:
    """Probed pids → block-table rows: ``(Q, nprobe*MB)`` block ids with
    -1 for absent pages and invalid probes."""
    pool = state.pool
    q = pids.shape[0]
    table = pool.posting_blocks[jnp.maximum(pids, 0)]   # (Q, nprobe, MB)
    table = jnp.where(((pids >= 0) & probe_valid)[..., None], table, -1)
    return table.reshape(q, -1)


def _page_slot_live(state: IndexState, pages: Array) -> tuple[Array, Array]:
    """Per-slot (vids, live) metadata for a set of pages ``(..., )`` →
    ``(..., BS)``.  The metadata gather is tiny (5 B/slot vs the d·dtype
    payload the Pallas kernel streams page-by-page)."""
    pool = state.pool
    with jax.named_scope("liveness"):
        safe = jnp.maximum(pages, 0)
        pvids = pool.block_vid[safe]
        pvers = pool.block_ver[safe]
        live = (
            (pages >= 0)[..., None]
            & (pvids >= 0)
            & ~vm.is_stale(state.versions, pvids, pvers)
        )
    return pvids, live


N_PAGE_COUNTS = 4


def _page_counts(
    flat: Array, counted: Array, member_pos: Array | None = None,
    budget: int = 0, n_scanned: Array | None = None,
) -> Array:
    """One dispatch's page accounting, int32 ``(pages scanned, pages
    dropped, grid, steps skipped)``, over the probes ``counted (Q,
    nprobe)`` marks (the search leaves padding rows out).  ``flat`` is the
    ``(Q, NB)`` page table.  Without ``member_pos`` (a per-query scan)
    every counted page is scanned, the grid is the table and no step is
    skipped; with the batched schedule's ``member_pos`` the distinct
    counted pages kept in its ``budget``-page grid, the counted pages it
    dropped, once per probing query, and the grid steps past the
    ``n_scanned`` pages the kernel scored (padding rows' pages included)."""
    mb = flat.shape[1] // counted.shape[1]
    real = (flat >= 0) & jnp.repeat(counted, mb, axis=1)
    if member_pos is None:
        return jnp.stack([jnp.sum(real), 0, flat.size, 0]).astype(jnp.int32)
    real = real.reshape(-1)
    tgt = jnp.where(real & (member_pos >= 0), member_pos, budget)
    kept = jnp.zeros((budget,), bool).at[tgt].set(True, mode="drop")
    dropped = jnp.sum(real & (member_pos < 0))
    return jnp.stack(
        [jnp.sum(kept), dropped, budget, budget - n_scanned]
    ).astype(jnp.int32)


def split_access(access):
    """``search(..., with_access=True)``'s third output → ``(probe
    histogram (num_postings_cap,), page counts (4,))``."""
    return access[:-N_PAGE_COUNTS], access[-N_PAGE_COUNTS:]


def _pallas_scan_candidates(
    state: IndexState, queries: Array, pids: Array, probe_valid: Array,
    counted: Array, *, k: int, schedule: str,
) -> tuple[Array, Array, Array, Array, Array]:
    """Paged Pallas posting scan → reduced candidate set.

    Streams SSD-block-sized pages through the ``posting_scan`` kernels and
    keeps only the per-page ``min(k, BS)`` nearest live candidates, so
    neither the (Q, nprobe·cap, d) gather buffer nor the (Q, nprobe·MB·BS)
    distance matrix ever exists in HBM.  Returns ``(dists (Q, n),
    vids (Q, n), pos (Q, n), live (Q, n), pages (4,))`` with n =
    pages·kpage; ``pos`` is each candidate's pool position
    (``block_id·BS + slot``, -1 dead), which the exact rerank gathers
    from the cold tier; ``pages`` is the dispatch's ``_page_counts`` over
    the probes ``counted`` marks.

    With the ``int8`` codec the dequant-fused kernel variants run instead:
    the probed posting's scale/zero ride the block-table DMA and the page
    is reconstructed on the VPU, so the page stream stays 1 byte/dim.

    ``schedule="per_query"`` streams every probed page once per query
    (paper-faithful ParallelGET).  ``schedule="batched"`` dedups the whole
    micro-batch's pages into a static grid (overflow drops the
    highest-numbered pages — see ``ops.dedup_pages``) and scores each
    unique page against all queries with one MXU GEMM; candidates are then
    masked back to each query's own probe set, so results match the
    per-query schedule whenever the grid holds every unique page.  The
    grid is the fewest pages the batch could probe, ``Q·nprobe·MB``, the
    pool's page count, or ``scan_page_budget`` when that caps it lower;
    the kernel stops at the dedup's distinct pages, and the grid steps
    past them fetch and score nothing.
    """
    cfg = state.cfg
    pool = state.pool
    q, nprobe = pids.shape
    mb = pool.max_blocks_per_posting
    bs = pool.block_size
    kpage = min(k, pool.block_size)
    quant = pool.codec == "int8"
    flat = _page_table(state, pids, probe_valid)        # (Q, NB)
    # posting owning each page row: pages j of probe i are i*MB..i*MB+MB-1
    page_pid = jnp.repeat(pids, mb, axis=1)             # (Q, NB)
    safe_pp = jnp.maximum(page_pid, 0)

    if schedule == "per_query":
        pvids, live = _page_slot_live(state, flat)      # (Q, NB, BS)
        if quant:
            d, slots = scan_ops.scan_posting_blocks_topk_q8(
                queries, flat, live, pool.blocks,
                pool.post_scale[safe_pp], pool.post_zero[safe_pp],
                k=kpage,
            )                                           # (Q, NB, kpage)
        else:
            d, slots = scan_ops.scan_posting_blocks_topk(
                queries, flat, live, pool.blocks, k=kpage
            )                                           # (Q, NB, kpage)
        cand_v = jnp.take_along_axis(pvids, slots, axis=2)
        cand_p = jnp.where(
            (flat >= 0)[:, :, None], flat[:, :, None] * bs + slots, -1
        )
        cand_d = d.reshape(q, -1)
        cand_v = cand_v.reshape(q, -1)
        cand_p = cand_p.reshape(q, -1)
        pages = _page_counts(flat, counted)
    elif schedule == "batched":
        # a bucket of q rows probes at most q·nprobe·MB pages: a larger
        # grid only adds steps no page can fill
        budget = min(q * nprobe * mb, cfg.num_blocks)
        if cfg.scan_page_budget:
            budget = min(budget, cfg.scan_page_budget)
        uniq, member_pos, n_unique, _ = scan_ops.dedup_pages(
            flat.reshape(-1), budget=budget, num_blocks=cfg.num_blocks
        )
        n_scan = jnp.minimum(n_unique, budget)
        pages = _page_counts(flat, counted, member_pos, budget, n_scan)
        pvids, live = _page_slot_live(state, uniq)      # (budget, BS)
        if quant:
            # invert the dedup: every original probe scatters its posting's
            # scale/zero onto its unique-page row (one posting owns each
            # block, so colliding writers carry identical values)
            fscale = pool.post_scale[safe_pp].reshape(-1)
            fzero = pool.post_zero[safe_pp].reshape(-1)
            tgt = jnp.where(member_pos >= 0, member_pos, budget)
            u_scale = jnp.ones((budget,), jnp.float32).at[tgt].set(
                fscale, mode="drop"
            )
            u_zero = jnp.zeros((budget,), jnp.float32).at[tgt].set(
                fzero, mode="drop"
            )
            d, slots = scan_ops.scan_unique_blocks_topk_q8(
                queries, uniq, live, pool.blocks, u_scale, u_zero,
                k=kpage, n_pages=n_scan,
            )                                           # (budget, kpage, Q)
        else:
            d, slots = scan_ops.scan_unique_blocks_topk(
                queries, uniq, live, pool.blocks, k=kpage, n_pages=n_scan
            )                                           # (budget, kpage, Q)
        # gather each query's own probed pages back out of the unique-page
        # tiles (parity with the per-query schedule: a page another query
        # probed must not leak in) — the reduce then sees the per-query
        # (Q, NB, kpage) candidate shape, NOT (Q, budget, kpage)
        mp = member_pos.reshape(q, -1)                  # (Q, NB)
        safe_mp = jnp.maximum(mp, 0)
        qi = jnp.arange(q)[:, None]
        slot_q = slots[safe_mp, :, qi]                  # (Q, NB, kpage)
        cand_d = jnp.where(
            (mp >= 0)[:, :, None], d[safe_mp, :, qi], MASK_DISTANCE
        ).reshape(q, -1)
        cand_v = jnp.take_along_axis(pvids[safe_mp], slot_q, axis=2)
        cand_v = cand_v.reshape(q, -1)
        cand_p = jnp.where(
            (mp >= 0)[:, :, None], uniq[safe_mp][:, :, None] * bs + slot_q,
            -1,
        ).reshape(q, -1)
    else:
        raise ValueError(
            f"scan_schedule must be 'per_query' or 'batched', got {schedule!r}"
        )
    return cand_d, cand_v, cand_p, cand_d < MASK_DISTANCE / 2, pages


def _posting_positions(pool, flat_pids: Array) -> Array:
    """Pool positions (``block_id·BS + slot``) of every capacity slot of
    the given postings: ``(m,)`` pids → ``(m, cap)``, -1 for absent
    blocks.  The rerank gathers exact payloads by these positions."""
    bids = pool.posting_blocks[flat_pids]               # (m, MB)
    slot = jnp.arange(pool.block_size, dtype=jnp.int32)
    pos = bids[..., None] * pool.block_size + slot[None, None, :]
    pos = jnp.where(bids[..., None] >= 0, pos, -1)
    return pos.reshape(flat_pids.shape[0], -1)


def _scan_probe_chunk(
    state: IndexState, queries: Array, pids: Array, probe_valid: Array
) -> tuple[Array, Array, Array, Array]:
    """Score one chunk of probed postings.  queries (Q, d); pids (Q, c).
    Returns (dists (Q, c*cap), vids, pos, live).

    Payloads come off the HOT tier (decoded through the posting codec) so
    the oracle computes the same distances as the dequant-fused Pallas
    scan — quantization error shows up identically on both data paths and
    the exact rerank removes it on both.
    """
    cfg = state.cfg
    q, c = pids.shape
    cap = cfg.posting_capacity
    flat_pids = jnp.maximum(pids.reshape(-1), 0)
    vecs, vids, vers, slot_valid = bp.parallel_get_hot(state.pool, flat_pids)
    pos = _posting_positions(state.pool, flat_pids)
    with jax.named_scope("liveness"):
        stale = vm.is_stale(state.versions, vids, vers)
        live = slot_valid & ~stale & probe_valid.reshape(-1)[:, None]
    vecs = vecs.reshape(q, c * cap, -1)
    vids = vids.reshape(q, c * cap)
    pos = pos.reshape(q, c * cap)
    live = live.reshape(q, c * cap)
    # scan math in cfg.scan_dtype (bf16 on TPU) with f32 accumulation —
    # halves the upcast traffic of int8 payloads (§Perf spfresh iter 2)
    sd = jnp.dtype(cfg.scan_dtype)
    qv = queries.astype(sd)
    xv = vecs.astype(sd)
    diff = qv[:, None, :] - xv
    dists = jnp.sum(
        (diff * diff).astype(jnp.float32), axis=-1
    )
    return dists, vids, pos, live


def _rerank_exact(
    state: IndexState, queries: Array, cand_d: Array, cand_v: Array,
    cand_pos: Array, k: int,
) -> tuple[Array, Array]:
    """Exact fp32 rerank of an over-fetched, already-deduped candidate set.

    ``cand_pos (Q, k')`` are pool positions; the cold exact tier is
    gathered (k'·d fp32 values per query — tiny next to the scan) and the
    final top-k runs on true distances.  Candidates arrive vid-deduped,
    so a plain top_k suffices.
    """
    pool = state.pool
    tier = pool.blocks_exact if pool.blocks_exact is not None else pool.blocks
    flat = tier.reshape(-1, pool.dim)
    safe = jnp.maximum(cand_pos, 0)
    vecs = flat[safe].astype(jnp.float32)               # (Q, k', d)
    qf = queries.astype(jnp.float32)
    diff = vecs - qf[:, None, :]
    dist = jnp.sum(diff * diff, axis=-1)
    dist = jnp.where((cand_pos >= 0) & (cand_v >= 0), dist, MASK_DISTANCE)
    neg, sel = jax.lax.top_k(-dist, k)
    top_d = -neg
    out_v = jnp.where(
        top_d < MASK_DISTANCE / 2,
        jnp.take_along_axis(cand_v, sel, axis=1),
        -1,
    )
    return top_d, out_v


def scan_and_reduce(
    state: IndexState,
    queries: Array,
    pids: Array,
    probe_valid: Array,
    *,
    k: int,
    probe_chunk: int = 0,
    use_pallas_scan: bool | None = None,
    scan_schedule: str | None = None,
    counted: Array | None = None,
) -> tuple[Array, Array, Array]:
    """Posting scan + dedup top-k over an already-navigated probe set.

    Shared by ``search`` and the grouped two-level search; the scan data
    path is selected here:

    * **Pallas paged scan** (``use_pallas_scan``, schedule per
      ``scan_schedule`` — both default to the config flags): pages stream
      HBM→VMEM through the ``posting_scan`` kernels, which emit per-page
      k-min candidates; the reduce then works on (Q, pages·kpage)
      candidates.  ``probe_chunk`` is ignored — the kernel grid already
      streams page-at-a-time, and the candidate buffer is k-reduced.
    * **XLA gather oracle** (default): ``bp.parallel_get_hot`` materializes
      the (Q, nprobe·cap, d) probe buffer (decoded hot tier);
      ``probe_chunk > 0`` processes the probes in chunks with a running
      candidate set so the buffer is O(Q · chunk · cap · d).

    With a lossy codec and ``cfg.rerank_factor > 1``, both data paths
    over-fetch ``rerank_factor × k`` deduped candidates from the
    quantized scan, then rerank them against the cold exact-fp32 tier
    before the final top-k (the two-tier search closing the accuracy
    gap).

    Returns ``(dists (Q, k), vids (Q, k), pages (4,))``: ``pages`` is the
    dispatch's page accounting (``_page_counts``; the XLA paths count
    every probed page of the grid as scanned) over the probes ``counted
    (Q, nprobe)`` marks, by default ``probe_valid``.  Device operations carry
    the named scopes ``scan`` (with ``liveness``, the version-map gather
    and bias, inside it) and ``reduce``.
    """
    cfg = state.cfg
    q, nprobe = pids.shape
    cap = cfg.posting_capacity
    pallas = cfg.use_pallas_scan if use_pallas_scan is None else use_pallas_scan
    schedule = scan_schedule if scan_schedule is not None else cfg.scan_schedule
    rerank = cfg.rerank_factor > 1 and state.pool.blocks_exact is not None
    kq = k * cfg.rerank_factor if rerank else k
    counted = probe_valid if counted is None else counted

    @jax.named_scope("reduce")
    def reduce_and_rerank(cand_d, cand_v, cand_p, live, pages):
        n = cand_d.shape[1]
        kk = min(kq, n) if rerank else k
        m = _dedup_prefilter(cfg, kk, n)
        d, v, oi = jax.vmap(
            lambda dd, vv, mm: _dedup_topk_1d_full(dd, vv, mm, kk, m)
        )(cand_d, cand_v, live)
        if not rerank:
            return d, v, pages
        pos = jnp.take_along_axis(cand_p, jnp.maximum(oi, 0), axis=1)
        pos = jnp.where(oi >= 0, pos, -1)
        return (*_rerank_exact(state, queries, d, v, pos, k), pages)

    if pallas:
        with jax.named_scope("scan"):
            cand = _pallas_scan_candidates(
                state, queries, pids, probe_valid, counted,
                k=kq, schedule=schedule,
            )
        return reduce_and_rerank(*cand)

    with jax.named_scope("scan"):
        pages = _page_counts(_page_table(state, pids, probe_valid), counted)
    if probe_chunk <= 0 or nprobe % probe_chunk != 0 or nprobe == probe_chunk:
        with jax.named_scope("scan"):
            dists, vids, pos, live = _scan_probe_chunk(
                state, queries, pids, probe_valid
            )
        return reduce_and_rerank(dists, vids, pos, live, pages)

    nc = nprobe // probe_chunk
    keep = min(max(4 * kq, 64), probe_chunk * cap)
    pids_c = pids.reshape(q, nc, probe_chunk).transpose(1, 0, 2)
    pvalid_c = probe_valid.reshape(q, nc, probe_chunk).transpose(1, 0, 2)

    def body(carry, inp):
        best_d, best_v, best_p = carry  # (Q, keep)
        pc, vc = inp
        d, v, p, live = _scan_probe_chunk(state, queries, pc, vc)
        d = jnp.where(live, d, MASK_DISTANCE)
        cat_d = jnp.concatenate([best_d, d], axis=1)
        cat_v = jnp.concatenate([best_v, v], axis=1)
        cat_p = jnp.concatenate([best_p, p], axis=1)
        neg, sel = jax.lax.top_k(-cat_d, keep)
        return (
            -neg,
            jnp.take_along_axis(cat_v, sel, axis=1),
            jnp.take_along_axis(cat_p, sel, axis=1),
        ), None

    init = (
        jnp.full((q, keep), MASK_DISTANCE, jnp.float32),
        jnp.full((q, keep), -1, jnp.int32),
        jnp.full((q, keep), -1, jnp.int32),
    )
    with jax.named_scope("scan"):
        (best_d, best_v, best_p), _ = jax.lax.scan(
            body, init, (pids_c, pvalid_c))
    live = best_d < MASK_DISTANCE / 2
    return reduce_and_rerank(best_d, best_v, best_p, live, pages)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "nprobe", "probe_chunk", "use_pallas_scan", "scan_schedule",
        "with_access",
    ),
)
def search(
    state: IndexState,
    queries: Array,
    *,
    k: int,
    nprobe: int | None = None,
    probe_chunk: int = 0,
    use_pallas_scan: bool | None = None,
    scan_schedule: str | None = None,
    with_access: bool = False,
    qvalid: Array | None = None,
) -> tuple[Array, ...]:
    """ANN search: centroid navigation → posting scan → dedup top-k.

    Returns ``(dists (Q, k), vids (Q, k))``; missing results are ``-1`` with
    MASK_DISTANCE.  ``nprobe`` is the latency-budget knob (the paper's 10 ms
    hard cut becomes a fixed candidate budget under jit).

    The posting-scan data path (Pallas paged streaming vs XLA gather, and
    the per-query vs batch-dedup page schedule) is selected by
    ``use_pallas_scan`` / ``scan_schedule`` — ``None`` defers to the
    config flags.  See ``scan_and_reduce`` for the probe_chunk semantics
    of the oracle path.

    ``with_access=True`` additionally returns, as a third output, the
    per-posting probe histogram (``probe_histogram``) with the dispatch's
    page counts appended, ``(pages scanned, pages dropped by the budget,
    grid, grid steps skipped)`` (``scan_and_reduce``; ``split_access``
    parts them): one readback carries both.  The ``(dists, vids)``
    numerics are untouched.  ``qvalid (Q,)`` masks padded query rows out of the
    histogram and the page counts ONLY (their dists/vids rows are
    computed regardless and discarded by the caller, as before).
    Navigation runs under the named scope ``navigate``.
    """
    cfg = state.cfg
    nprobe = cfg.nprobe if nprobe is None else nprobe
    with jax.named_scope("navigate"):
        nav_d, pids = navigate(state, queries, nprobe)  # (Q, nprobe)
        probe_valid = nav_d < MASK_DISTANCE / 2
    counted = probe_valid if qvalid is None else probe_valid & qvalid[:, None]
    d, v, pages = scan_and_reduce(
        state, queries, pids, probe_valid,
        k=k, probe_chunk=probe_chunk,
        use_pallas_scan=use_pallas_scan, scan_schedule=scan_schedule,
        counted=counted,
    )
    if not with_access:
        return d, v
    return d, v, jnp.concatenate([probe_histogram(cfg, pids, counted), pages])


# ---------------------------------------------------------------------------
# Reassignment execution (shared by split and merge)
# ---------------------------------------------------------------------------

def _dedup_vid_mask_ref(vids: Array, mask: Array) -> Array:
    """Reference same-vid dedup (the original O(n²) pairwise mask, kept as
    the oracle for tests and the before/after benchmark): a masked row is
    dropped when any earlier-indexed masked row carries the same vid."""
    n = vids.shape[0]
    idx = jnp.arange(n)
    same = (vids[:, None] == vids[None, :]) & (
        idx[:, None] > idx[None, :]
    )
    dup = jnp.any(same & mask[None, :], axis=1)
    return mask & ~dup


def _dedup_vid_mask(vids: Array, mask: Array) -> Array:
    """First-occurrence-per-vid filter over the masked rows.

    Sort-based idiom (the `_dedup_topk_1d` rewrite applied to the reassign
    batch): one stable argsort on a masked key instead of the O(n²)
    pairwise comparison matrix.  Unmasked rows key to a sentinel so they
    never suppress a masked row; within a vid group the stable sort keeps
    the lowest original index — exactly the reference semantics.
    """
    n = vids.shape[0]
    key = jnp.where(mask, vids, jnp.iinfo(jnp.int32).max)
    order = jnp.argsort(key, stable=True)
    sk = key[order]
    first = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    return mask & jnp.zeros((n,), bool).at[order].set(first)


def _execute_reassigns(
    state: IndexState,
    cand_vecs: Array,   # (C, d)
    cand_vids: Array,   # (C,)
    cand_cur_pid: Array,  # (C,) posting the candidate currently lives in
    cand_mask: Array,   # (C,) passed the necessary conditions
    budget: int | None = None,
) -> IndexState:
    """Paper §3.3 final stage: per candidate, search the new closest posting,
    NPA-recheck to drop false positives, then version-bump + re-append.

    Candidates are compacted to ``budget`` rows (default
    ``cfg.reassign_budget``; overflow counted — the paper reports ~79
    actual reassigns out of ~5094 evaluated, so the budget is generous).
    The maintenance round concatenates EVERY job's candidates into one
    call here with a jobs-scaled budget, so the whole round pays one
    routing GEMM and one `append_scatter` instead of two per job.
    """
    cfg = state.cfg
    c = cand_vecs.shape[0]
    budget = min(budget or cfg.reassign_budget, c)

    # --- compact to the evaluation budget ---
    order = jnp.argsort(~cand_mask, stable=True)  # True (mask) rows first
    take = order[:budget]
    vecs = cand_vecs[take]
    vids = cand_vids[take]
    cur_pid = cand_cur_pid[take]
    mask = cand_mask[take]
    n_cand = jnp.sum(cand_mask)
    overflow = jnp.maximum(n_cand - budget, 0)

    # --- dedup same vid within the batch (concurrent-reassign CAS analogue) ---
    mask = _dedup_vid_mask(vids, mask)
    # Deleted/stale ids never get reassigned (they get GC'd instead).
    mask = mask & ~vm.is_deleted(state.versions, jnp.maximum(vids, 0)) & (vids >= 0)

    # --- NPA re-check: find the true nearest posting now ---
    # The re-check only needs the argmin posting, not the full top-R
    # closure routing — a masked argmin over the (budget × P) GEMM, so the
    # (sort-backed, CPU-hostile) masked top-k runs only on the compacted
    # movers below.
    d_all = pairwise_sql2(vecs, state.centroids, state.centroid_sqn)
    d_all = jnp.where(state.centroid_valid[None, :], d_all, MASK_DISTANCE)
    nearest = jnp.argmin(d_all, axis=1).astype(jnp.int32)
    nearest = jnp.where(
        jnp.min(d_all, axis=1) < MASK_DISTANCE / 2, nearest, -1
    )
    # False-positive filter (paper: "if a vector actually does not need
    # reassignment, the reassign operation is aborted"): if a LIVE replica of
    # this vid already sits in the nearest posting, NPA is satisfied.
    safe_vids = jnp.maximum(vids, 0)
    cur_ver = state.versions[safe_vids] & vm.VERSION_MASK
    t_vids, t_vers, t_valid = jax.vmap(
        lambda p: bp.gather_posting_ids(state.pool, p)
    )(jnp.maximum(nearest, 0))  # (budget, cap)
    replica_there = jnp.any(
        (t_vids == vids[:, None])
        & t_valid
        & ((t_vers & vm.VERSION_MASK) == cur_ver[:, None]),
        axis=-1,
    )
    need = mask & (nearest >= 0) & (nearest != cur_pid) & ~replica_there

    # --- compact the actual MOVERS to reassign_budget candidates ---
    # The paper reports ~79 movers out of ~5094 evaluated, so the write
    # path is sized for the movers, not the evaluation budget: the fused
    # round evaluates its jobs-scaled candidate budget with the GEMMs
    # above, but at most reassign_budget vectors move per pass (the knob's
    # original meaning) — keeping the append scatter, the scarcest op on
    # CPU/TPU alike, at a fixed small row count.  Truncated movers simply
    # stay where they are (counted as overflow; live replicas untouched).
    movers = min(cfg.reassign_budget, budget)
    morder = jnp.argsort(~need, stable=True)
    mtake = morder[:movers]
    m_vecs = vecs[mtake]
    m_vids = vids[mtake]
    m_safe_vids = safe_vids[mtake]
    m_cur_ver = cur_ver[mtake]
    m_need = need[mtake]
    n_need = jnp.sum(need)
    overflow = overflow + jnp.maximum(n_need - movers, 0)
    # Full closure routing (top-R + replica rule) for the movers only.
    m_pids, _, m_replica_ok = route(state, m_vecs, cfg.replica_count)

    # --- append fresh replicas at the new homes with a TENTATIVE version ---
    # The version map is only bumped if the primary append lands; otherwise
    # the old replicas stay live (no data loss when the target is full) and
    # the tentative appends are stale garbage, GC'd by the next split.
    tentative_ver = (m_cur_ver + 1) & vm.VERSION_MASK
    enable = m_need[:, None] & m_replica_ok & (m_pids >= 0)
    flat_pids = jnp.maximum(m_pids.reshape(-1), 0)
    flat_enable = enable.reshape(-1)
    flat_vecs = jnp.repeat(m_vecs, cfg.replica_count, axis=0)
    flat_vids = jnp.repeat(m_vids, cfg.replica_count)
    flat_vers = jnp.repeat(tentative_ver, cfg.replica_count)
    # collision-ranked scatter append: the whole (movers·R)-row batch lands
    # in one dispatch instead of a movers·R-step tail-write scan
    pool, oks = bp.append_scatter(
        state.pool, flat_pids, flat_vecs, flat_vids, flat_vers, flat_enable
    )
    landed = oks.reshape(-1, cfg.replica_count)[:, 0]
    commit = m_need & landed
    versions = vm.bump_version(state.versions, m_safe_vids, commit)
    telemetry = _bump_append_telemetry(state, flat_pids, flat_vecs, oks)
    state = state.replace(versions=versions, telemetry=telemetry)

    stats = state.stats
    stats = bump_stat(stats, "n_reassign_candidates", n_cand)
    stats = bump_stat(stats, "n_reassign_overflow", overflow)
    stats = bump_stat(stats, "n_reassigned", jnp.sum(commit))
    stats = bump_stat(stats, "n_appends", jnp.sum(oks))
    stats = bump_stat(
        stats, "n_append_drops", jnp.sum(flat_enable) - jnp.sum(oks)
    )
    return state.replace(pool=pool, stats=stats)


# ---------------------------------------------------------------------------
# Split (Local Rebuilder job, §4.2.1) — batched K-job core + K=1 wrapper
# ---------------------------------------------------------------------------

def _split_jobs(
    state: IndexState, pids: Array, enable: Array
) -> tuple[IndexState, Array, tuple[Array, Array, Array, Array]]:
    """K split jobs in one fused pass.  ``pids (K,)`` must be distinct.

    Per job: GC the posting; if still oversized, balanced-2-means split
    into two fresh postings.  All K jobs share one vmapped
    `balanced_two_means`, one batched pid alloc, one `free_postings`
    scatter, ONE `put_postings` scatter for every half-write and GC
    write-back, and one ``(K × P)`` neighbor GEMM.

    Returns ``(state, acted (K,), (cand_vecs, cand_vids, cand_cur,
    cand_mask))`` — the flattened reassign candidates
    (``K·(1+reassign_range)·cap`` rows) for the caller's fused
    `_execute_reassigns`.
    """
    cfg = state.cfg
    cap = cfg.posting_capacity
    k = pids.shape[0]
    pids = pids.astype(jnp.int32)
    safe = jnp.maximum(pids, 0)
    enable = enable & (pids >= 0) & state.centroid_valid[safe]

    vecs, vids, vers, valid = bp.gather_postings(state.pool, safe)  # (K, cap, ...)
    live = valid & ~vm.is_stale(state.versions, vids, vers)
    n_live = jnp.sum(live, axis=1)                       # (K,)
    cur_len = state.pool.posting_len[safe]
    cur_ver = state.versions[jnp.maximum(vids, 0)] & vm.VERSION_MASK

    # ---- Case A: garbage-collection write-back resolves the job ----
    gc_wb = enable & (n_live <= cfg.split_limit) & (n_live < cur_len)
    order_live = jnp.argsort(~live, axis=1, stable=True)
    gc_vecs = jnp.take_along_axis(vecs, order_live[..., None], axis=1)
    gc_vids = jnp.take_along_axis(vids, order_live, axis=1)
    gc_vers = jnp.take_along_axis(cur_ver, order_live, axis=1)

    # ---- Case B: real split ----
    want = enable & (n_live > cfg.split_limit)
    if not cfg.enable_split:
        want = jnp.zeros_like(want)
    rng, sub = jax.random.split(state.rng)
    state = state.replace(rng=rng)
    new_centroids, assign = jax.vmap(
        lambda key, x, lv: balanced_two_means(
            key, x, lv, iters=cfg.kmeans_iters
        )
    )(jax.random.split(sub, k), vecs.astype(jnp.float32), live)
    # new_centroids (K, 2, d); assign (K, cap) in {-1, 0, 1}

    state, new_pids = alloc_pids(state, jnp.repeat(want, 2))  # (2K,)
    pid1, pid2 = new_pids[0::2], new_pids[1::2]
    ok = want & (pid1 >= 0) & (pid2 >= 0)
    # Roll back half-successful allocations (pid1 landed, pid2 didn't).
    state = free_pids(state, new_pids, jnp.repeat(want & ~ok, 2))

    old_centroid = state.centroids[safe]                 # (K, d)
    old_access = state.telemetry.access_count[safe]      # (K,) read pre-free

    # Retire the old postings (blocks + centroids + ids) in one scatter.
    pool = bp.free_postings(state.pool, safe, ok)
    state = state.replace(pool=pool)
    state = free_pids(state, pids, ok)

    # Halves, compacted to the front of fixed-capacity buffers.
    in0 = live & (assign == 0)
    in1 = live & (assign == 1)
    n0 = jnp.sum(in0, axis=1)
    n1 = jnp.sum(in1, axis=1)
    order0 = jnp.argsort(~in0, axis=1, stable=True)
    order1 = jnp.argsort(~in1, axis=1, stable=True)

    def _take(buf, order):
        if buf.ndim == 3:
            return jnp.take_along_axis(buf, order[..., None], axis=1)
        return jnp.take_along_axis(buf, order, axis=1)

    # ONE put scatter: K GC write-backs (old pid) + 2K half-writes (fresh
    # pids) — all target pids distinct among enabled rows.
    put_pids = jnp.concatenate([safe, jnp.maximum(pid1, 0), jnp.maximum(pid2, 0)])
    put_vecs = jnp.concatenate(
        [gc_vecs, _take(vecs, order0), _take(vecs, order1)], axis=0
    )
    put_vids = jnp.concatenate(
        [gc_vids, _take(vids, order0), _take(vids, order1)], axis=0
    )
    put_vers = jnp.concatenate(
        [gc_vers, _take(cur_ver, order0), _take(cur_ver, order1)], axis=0
    )
    put_ns = jnp.concatenate([n_live, n0, n1])
    put_en = jnp.concatenate([gc_wb, ok, ok])
    pool, _ = bp.put_postings(
        state.pool, put_pids, put_vecs, put_vids, put_vers, put_ns, put_en
    )
    state = state.replace(pool=pool)
    state = set_centroids(state, pid1, new_centroids[:, 0], ok)
    state = set_centroids(state, pid2, new_centroids[:, 1], ok)

    # Telemetry transfer: the two fresh halves inherit the split posting's
    # access count proportionally to their live sizes (integer shares that
    # conserve the total exactly); update_count/drift_vec measure "since
    # last split", so the halves restart at zero — fresh pids come off the
    # free stack already zeroed (`free_pids`).
    tot = jnp.maximum(n0 + n1, 1)
    share1 = (old_access * n0) // tot
    share2 = old_access - share1
    cap_p = cfg.num_postings_cap
    t1 = jnp.where(ok, jnp.maximum(pid1, 0), cap_p)
    t2 = jnp.where(ok, jnp.maximum(pid2, 0), cap_p)
    acc = state.telemetry.access_count.at[t1].set(share1, mode="drop")
    acc = acc.at[t2].set(share2, mode="drop")
    state = state.replace(
        telemetry=state.telemetry.replace(access_count=acc)
    )

    # ---- Reassignment candidates (the heart of LIRE) ----
    # Neighbors: reassign_range nearest postings to each *old* centroid,
    # excluding the job's own two fresh halves — one (K × P) GEMM instead
    # of K skinny (1 × P) ones.
    nb_d = pairwise_sql2(old_centroid, state.centroids, state.centroid_sqn)
    arange_p = jnp.arange(cfg.num_postings_cap)
    nb_valid = (
        state.centroid_valid[None, :]
        & (arange_p[None, :] != jnp.maximum(pid1, 0)[:, None])
        & (arange_p[None, :] != jnp.maximum(pid2, 0)[:, None])
    )
    nb_dist, nb_pids = masked_topk(nb_d, nb_valid, cfg.reassign_range)
    nb_ok = nb_dist < MASK_DISTANCE / 2                  # (K, RR)

    nvecs, nvids, nvers, nvalid = bp.gather_postings(
        state.pool, nb_pids.reshape(-1)
    )  # (K·RR, cap, ...)
    nlive = nvalid & ~vm.is_stale(state.versions, nvids, nvers)
    nlive = nlive & nb_ok.reshape(-1)[:, None] & jnp.repeat(ok, cfg.reassign_range)[:, None]

    # Eq. (2) for neighbor vectors; Eq. (1) for the split posting's vectors.
    eq2 = jax.vmap(npa.split_neighbor_candidates)(
        nvecs.reshape(k, -1, cfg.dim).astype(jnp.float32),
        old_centroid,
        new_centroids,
    ).reshape(k * cfg.reassign_range, cap)
    eq1 = jax.vmap(npa.split_old_posting_candidates)(
        vecs.astype(jnp.float32), old_centroid, new_centroids
    )  # (K, cap)
    own_cur = jnp.where(
        assign == 0, jnp.maximum(pid1, 0)[:, None], jnp.maximum(pid2, 0)[:, None]
    )

    cand_vecs = jnp.concatenate(
        [vecs.reshape(-1, cfg.dim), nvecs.reshape(-1, cfg.dim)], axis=0
    )
    cand_vids = jnp.concatenate([vids.reshape(-1), nvids.reshape(-1)])
    cand_cur = jnp.concatenate(
        [own_cur.reshape(-1), jnp.repeat(nb_pids.reshape(-1), cap)]
    )
    cand_mask = jnp.concatenate(
        [(eq1 & live & ok[:, None]).reshape(-1), (eq2 & nlive).reshape(-1)]
    )

    checked = jnp.sum(jnp.where(ok, n_live, 0)) + jnp.sum(nlive)
    stats = bump_stat(state.stats, "n_reassign_checked", checked)
    stats = bump_stat(stats, "n_splits", jnp.sum(ok))
    stats = bump_stat(stats, "n_gc_writebacks", jnp.sum(gc_wb))
    state = state.replace(stats=stats, step=state.step + 1)
    return state, (ok | gc_wb), (cand_vecs, cand_vids, cand_cur, cand_mask)


@jax.jit
def split_posting(
    state: IndexState, pid: Array, enable: Array
) -> tuple[IndexState, Array]:
    """Split job: GC the posting; if still oversized, balanced-2-means split,
    then LIRE reassignment over the split + ``reassign_range`` neighbors.

    K=1 wrapper over the batched `_split_jobs` core (the maintenance round
    runs K of these fused); returns ``(state, acted)`` where acted covers
    both GC-writeback and true splits.
    """
    pid = jnp.asarray(pid, jnp.int32).reshape(1)
    enable = jnp.asarray(enable).reshape(1)
    state, acted, cand = _split_jobs(state, pid, enable)
    if state.cfg.enable_reassign:
        state = _execute_reassigns(state, *cand)
    return state, acted[0]


# ---------------------------------------------------------------------------
# Merge (Local Rebuilder job, §3.2 / §4.2.1) — batched K-job core + wrapper
# ---------------------------------------------------------------------------

def _merge_jobs(
    state: IndexState, pids: Array, enable: Array, exclude_pids: Array
) -> tuple[IndexState, Array, tuple[Array, Array, Array, Array]]:
    """K merge jobs in one fused pass.  ``pids (K,)`` must be distinct.

    Target selection (nearest of the ``merge_fanout`` closest postings with
    room) is one ``(K × P)`` GEMM; the moves land through ONE
    `append_scatter` over the K·cap concatenated rows, whose per-posting
    collision ranks keep per-append capacity safety when two jobs pick the
    same target.  ``exclude_pids`` are barred as targets — the round
    passes every merge source, since a source freed later in the round
    must not absorb another job's vectors.

    Returns ``(state, gone (K,), (cand_vecs, cand_vids, cand_cur,
    cand_mask))`` — the moved vectors as reassign candidates.
    """
    cfg = state.cfg
    k = pids.shape[0]
    pids = pids.astype(jnp.int32)
    safe = jnp.maximum(pids, 0)
    enable = enable & (pids >= 0) & state.centroid_valid[safe]

    vecs, vids, vers, valid = bp.gather_postings(state.pool, safe)
    live = valid & ~vm.is_stale(state.versions, vids, vers)
    n_live = jnp.sum(live, axis=1)                       # (K,)
    enable = enable & (n_live < cfg.merge_limit)

    # Nearest postings able to absorb each job: try the merge_fanout closest.
    own_centroid = state.centroids[safe]                 # (K, d)
    d = pairwise_sql2(own_centroid, state.centroids, state.centroid_sqn)
    arange_p = jnp.arange(cfg.num_postings_cap)
    ex = exclude_pids.astype(jnp.int32)
    excluded = jnp.any(
        (arange_p[:, None] == ex[None, :]) & (ex >= 0)[None, :], axis=1
    )
    cand_ok = state.centroid_valid & ~excluded           # (P,)
    cd, cpids = masked_topk(
        d, jnp.broadcast_to(cand_ok[None, :], d.shape), cfg.merge_fanout
    )
    fits = (cd < MASK_DISTANCE / 2) & (
        state.pool.posting_len[jnp.maximum(cpids, 0)] + n_live[:, None]
        <= cfg.posting_capacity
    )
    any_fit = jnp.any(fits, axis=1)
    first_fit = jnp.argmax(fits, axis=1)                 # first True per job
    target = jnp.where(
        any_fit, jnp.take_along_axis(cpids, first_fit[:, None], axis=1)[:, 0], -1
    )
    do = enable & any_fit & (n_live > 0)
    # Shared-target capacity: `fits` was checked against the pre-append
    # lengths, so two jobs absorbing into the same posting could together
    # overflow it and leak a partially-landed (live, unreclaimable) copy.
    # Charge each job the load of every EARLIER move candidate on the same
    # target (conservative: earlier candidates later dropped still count)
    # and defer jobs that no longer fit to the next round.
    jidx = jnp.arange(k)
    same_t = (target[:, None] == target[None, :]) & (target >= 0)[:, None]
    prior = jnp.sum(
        jnp.where(
            same_t & (jidx[:, None] > jidx[None, :]) & do[None, :],
            n_live[None, :], 0,
        ),
        axis=1,
    )
    do = do & (
        state.pool.posting_len[jnp.maximum(target, 0)] + prior + n_live
        <= cfg.posting_capacity
    )
    # Empty postings are simply retired.
    retire_empty = enable & (n_live == 0)

    cur_ver = state.versions[jnp.maximum(vids, 0)] & vm.VERSION_MASK
    move = live & do[:, None]
    tgt_rows = jnp.broadcast_to(jnp.maximum(target, 0)[:, None], (k, vecs.shape[1]))
    pool, oks = bp.append_scatter(
        state.pool,
        tgt_rows.reshape(-1),
        vecs.reshape(-1, cfg.dim),
        vids.reshape(-1),
        cur_ver.reshape(-1),
        move.reshape(-1),
    )
    state = state.replace(pool=pool)

    # Retire the merged-away postings — only where every live vector landed
    # in the target (pool OOM mid-merge must not lose vectors).
    all_moved = jnp.all(oks.reshape(k, -1) == move, axis=1)
    do = do & all_moved
    gone = do | retire_empty

    # Telemetry: the moves are fresh appends on the target (+1 update,
    # += displacement vs the TARGET centroid, which a merge never moves);
    # an absorbed source's access count transfers into its target — a
    # scatter-add, since two jobs may share one target — BEFORE the source
    # pid is freed (free_pids zeroes the source rows).  retire_empty
    # sources have nothing left to describe; their access just drops.
    tel = _bump_append_telemetry(
        state, tgt_rows.reshape(-1), vecs.reshape(-1, cfg.dim), oks
    )
    src_access = tel.access_count[safe]
    t_acc = jnp.where(do, jnp.maximum(target, 0), cfg.num_postings_cap)
    tel = tel.replace(
        access_count=tel.access_count.at[t_acc].add(
            jnp.where(do, src_access, 0), mode="drop"
        )
    )
    state = state.replace(telemetry=tel)

    pool = bp.free_postings(state.pool, safe, gone)
    state = state.replace(pool=pool)
    state = free_pids(state, pids, gone)

    # Reassign check over moved vectors only (no neighbor scan for merges).
    state = state.replace(
        stats=bump_stat(
            bump_stat(state.stats, "n_merges", jnp.sum(do)),
            "n_reassign_checked", jnp.sum(jnp.where(do, n_live, 0)),
        ),
        step=state.step + 1,
    )
    cand_cur = tgt_rows.reshape(-1)
    cand_mask = (live & do[:, None]).reshape(-1)
    return state, gone, (
        vecs.reshape(-1, cfg.dim), vids.reshape(-1), cand_cur, cand_mask
    )


@jax.jit
def merge_posting(
    state: IndexState, pid: Array, enable: Array
) -> tuple[IndexState, Array]:
    """Merge job: append the undersized posting's live vectors into the
    nearest posting that can hold them, delete its centroid, then run the
    (neighbor-free) reassignment check over the moved vectors.

    K=1 wrapper over the batched `_merge_jobs` core.
    """
    pid = jnp.asarray(pid, jnp.int32).reshape(1)
    enable = jnp.asarray(enable).reshape(1)
    state, gone, cand = _merge_jobs(state, pid, enable, pid)
    if state.cfg.enable_reassign:
        state = _execute_reassigns(state, *cand)
    return state, gone[0]


# ---------------------------------------------------------------------------
# Maintenance driver (the Local Rebuilder queue, discovered by length scan)
# ---------------------------------------------------------------------------

@jax.jit
def maintenance_step(state: IndexState) -> tuple[IndexState, Array]:
    """One background rebuild step: split the most oversized posting (if
    any), merge the most undersized (if any).  Constant work; returns
    ``(state, did_work)``.

    The §3.4 convergence argument bounds how many steps a driver loop needs:
    each split consumes a free posting id, so ``P_cap`` is a hard bound on
    cascade length.  `maintenance_round` is the batched K-job form.
    """
    cfg = state.cfg
    lens = state.pool.posting_len
    valid = state.centroid_valid

    split_scores = jnp.where(valid, lens, -1)
    split_pid = jnp.argmax(split_scores).astype(jnp.int32)
    want_split = split_scores[split_pid] > cfg.split_limit
    state, split_acted = split_posting(state, split_pid, want_split)

    merge_scores = jnp.where(
        valid & (lens < cfg.merge_limit), lens, jnp.iinfo(jnp.int32).max
    )
    merge_pid = jnp.argmin(merge_scores).astype(jnp.int32)
    want_merge = merge_scores[merge_pid] < cfg.merge_limit
    if not cfg.enable_merge:
        want_merge = jnp.asarray(False)
    state, merge_acted = merge_posting(state, merge_pid, want_merge)

    return state, (split_acted | merge_acted)


def _select_jobs(
    state: IndexState, k: int
) -> tuple[Array, Array, Array, Array]:
    """Job selection for one maintenance round, per ``cfg.maintain_policy``.

    ``"size"`` is the original selection, kept **bit-identical**: top-K
    longest postings split, bottom-K shortest merge.  ``"drift"`` is the
    Ada-IVF-style cost model over the telemetry leaves: *eligibility* is
    unchanged (only oversized postings may split, only undersized merge),
    but the *ranking* among eligible postings weighs access rate and
    centroid drift —

    * split priority = ``imbalance · (1 + alpha·access_rate) +
      beta·drift_rel`` where ``imbalance = len/split_limit``,
      ``access_rate`` is the posting's share of probes normalized so a
      uniformly-probed index scores 1 everywhere, and ``drift_rel`` is the
      mean displacement of appends since the last split relative to the
      centroid norm;
    * merge priority = ``len · (1 + alpha·access_rate)`` ascending —
      coldest+smallest first, so rarely-read runts are compacted before
      hot ones whose vectors searches still want cheap to find.

    With all-zero telemetry both formulas reduce to a monotone function of
    ``len`` — the drift policy cold-starts to the size ordering exactly
    (including ``top_k``'s lowest-index tie-breaking).

    Returns ``(split_pids, split_enable, merge_pids, merge_enable)``.
    """
    cfg = state.cfg
    lens = state.pool.posting_len
    valid = state.centroid_valid

    if cfg.maintain_policy == "size":
        # One length scan selects both job sets.
        split_scores = jnp.where(valid, lens, -1)
        top_l, split_pids = jax.lax.top_k(split_scores, k)
        split_enable = top_l > cfg.split_limit

        merge_scores = jnp.where(
            valid & (lens < cfg.merge_limit), lens, jnp.iinfo(jnp.int32).max
        )
        neg_l, merge_pids = jax.lax.top_k(-merge_scores, k)
        merge_enable = (-neg_l) < cfg.merge_limit
        return split_pids, split_enable, merge_pids, merge_enable

    tel = state.telemetry
    alpha = jnp.float32(cfg.maintain_alpha)
    beta = jnp.float32(cfg.maintain_beta)
    lens_f = lens.astype(jnp.float32)
    acc = jnp.where(valid, tel.access_count, 0).astype(jnp.float32)
    n_valid = jnp.sum(valid.astype(jnp.int32)).astype(jnp.float32)
    access_rate = acc * n_valid / jnp.maximum(jnp.sum(acc), 1.0)
    mean_disp = jnp.linalg.norm(tel.drift_vec, axis=-1) / jnp.maximum(
        tel.update_count.astype(jnp.float32), 1.0
    )
    drift_rel = mean_disp / jnp.sqrt(state.centroid_sqn + 1e-6)

    imbalance = lens_f / jnp.float32(cfg.split_limit)
    split_pri = imbalance * (1.0 + alpha * access_rate) + beta * drift_rel
    s_scores = jnp.where(
        valid & (lens > cfg.split_limit), split_pri, -jnp.inf
    )
    top_s, split_pids = jax.lax.top_k(s_scores, k)
    split_enable = top_s > -jnp.inf

    merge_pri = lens_f * (1.0 + alpha * access_rate)
    m_scores = jnp.where(
        valid & (lens < cfg.merge_limit), merge_pri, jnp.inf
    )
    neg_m, merge_pids = jax.lax.top_k(-m_scores, k)
    merge_enable = -neg_m < jnp.inf
    return split_pids, split_enable, merge_pids, merge_enable


def _split_forced_first(
    state: IndexState, split_pids: Array, split_enable: Array, force: Array
) -> tuple[Array, Array]:
    """The round's ``K`` split jobs with the postings ``force (F,)`` names
    in front: each named posting still valid and over ``split_limit``
    takes one of the first slots (once, in ``force`` order), and the
    ranked jobs ``split_pids``/``split_enable`` fill the slots left, in
    their order, skipping the postings already forced.  The ``-1``
    entries of ``force`` name nothing; with none named the jobs come back
    as they were.  Every returned pid is distinct, as `_split_jobs`
    requires."""
    cfg = state.cfg
    k = split_pids.shape[0]
    force = force.astype(jnp.int32)
    safe = jnp.maximum(force, 0)
    named = (
        (force >= 0) & state.centroid_valid[safe]
        & (state.pool.posting_len[safe] > cfg.split_limit)
    )
    named = _dedup_vid_mask(force, named)
    forced = jnp.zeros((cfg.num_postings_cap,), bool).at[
        jnp.where(named, force, cfg.num_postings_cap)
    ].set(True, mode="drop")
    dup = forced[split_pids]
    # slot order: forced, ranked jobs, the ranking's disabled filler, and
    # last the entries no slot may take (duplicates, names of nothing)
    rank = jnp.concatenate([
        jnp.where(named, 0, 4),
        jnp.where(dup, 3, jnp.where(split_enable, 1, 2)),
    ])
    order = jnp.argsort(rank, stable=True)[:k]
    pids = jnp.concatenate([safe, split_pids.astype(jnp.int32)])
    enable = jnp.concatenate([named, split_enable & ~dup])
    return pids[order], enable[order]


@functools.partial(jax.jit, static_argnames=("jobs_per_round",))
def maintenance_round(
    state: IndexState,
    jobs_per_round: int | None = None,
    access: Array | None = None,
    force: Array | None = None,
) -> tuple[IndexState, Array]:
    """One batched rebuild round: K split + K merge jobs selected by
    ``cfg.maintain_policy`` (see `_select_jobs`; disjoint pid sets —
    ``merge_limit < split_limit``), then every job's reassign candidates
    are concatenated into ONE `_execute_reassigns` call — one ``route``
    GEMM and one ``append_batch`` for the whole round instead of two per
    job.

    Returns ``(state, n_did_work)`` — the number of jobs that acted, ONE
    device scalar for the host drain loop to read back per round (the
    sequential driver synced on a bool per step).  ``jobs_per_round=None``
    defers to ``cfg.jobs_per_round``.

    ``access`` is an optional ``(P_cap,) i32`` probe histogram (the
    serving backend's host-accumulated search telemetry, WAL-logged with
    this dispatch) folded into ``telemetry.access_count`` BEFORE
    selection.  ``None`` skips the fold entirely — an empty pytree keys
    its own jit cache entry, so pre-telemetry call sites and old WAL
    records trace byte-identical graphs.

    ``force`` is an optional ``(F,) i32`` list of postings to split first
    (-1 = none): the Updater's backpressure names the full primary
    postings of the inserts it is retrying, and each of them still over
    ``split_limit`` takes one of the round's first split slots ahead of
    the ranking (`_split_forced_first`).  All -1 selects exactly what
    ``None`` does.
    """
    with jax.named_scope("maintain"):
        cfg = state.cfg
        k = int(jobs_per_round or cfg.jobs_per_round)
        k = max(1, min(k, cfg.num_postings_cap // 2))

        if access is not None:
            tel = state.telemetry
            state = state.replace(
                telemetry=tel.replace(
                    access_count=tel.access_count + access.astype(jnp.int32)
                )
            )

        split_pids, split_enable, merge_pids, merge_enable = _select_jobs(state, k)
        if force is not None:
            split_pids, split_enable = _split_forced_first(
                state, split_pids, split_enable, force
            )
        if not cfg.enable_merge:
            merge_enable = jnp.zeros_like(merge_enable)

        state, split_acted, s_cand = _split_jobs(
            state, split_pids.astype(jnp.int32), split_enable
        )
        # Merges run after the splits (freed split pids are already invalid, so
        # they can't be picked as absorb targets); every ENABLED merge source
        # is barred as a target for every job — disabled rows are top_k filler
        # indices that must stay eligible as targets.
        state, merge_acted, m_cand = _merge_jobs(
            state, merge_pids.astype(jnp.int32), merge_enable,
            jnp.where(merge_enable, merge_pids, -1).astype(jnp.int32),
        )

        if cfg.enable_reassign:
            cand = tuple(
                jnp.concatenate([a, b], axis=0) for a, b in zip(s_cand, m_cand)
            )
            # Evaluation budget scales with the round's job count (overflow is
            # counted); the mover compaction inside keeps the append scatter at
            # reassign_budget rows regardless.  One wide GEMM + one scatter for
            # the whole round instead of two of each per job.
            state = _execute_reassigns(
                state, *cand,
                budget=max(cfg.reassign_budget, k * cfg.reassign_budget // 2),
            )

        did = jnp.sum(split_acted.astype(jnp.int32)) + jnp.sum(
            merge_acted.astype(jnp.int32)
        )
        return state, did


@functools.lru_cache(maxsize=None)
def _donating_round(jobs: int):
    """State-donating compile of `maintenance_round` (drain loops hand the
    round its own state back, so XLA updates the block pool in place
    instead of copying it every round); ``access`` and ``force`` may be
    None, as in `maintenance_round`."""
    return jax.jit(
        lambda s, a, f: maintenance_round(s, jobs, a, f), donate_argnums=(0,)
    )


def rebuild_drain(
    state: IndexState,
    max_steps: int | None = None,
    jobs_per_round: int | None = None,
    *,
    donate: bool = False,
    access: Array | None = None,
    force: Array | None = None,
) -> tuple[IndexState, int, int]:
    """Host-driven Local Rebuilder loop in batched rounds: run
    `maintenance_round` until quiescent, reading back ONE ``did_work``
    scalar per round (the old loop host-synced on a bool after every
    split+merge step).  Bounded by the convergence proof (≤ P_cap splits
    possible).

    ``max_steps`` caps the total jobs executed (the pre-round "steps"
    budget; the last round may overshoot by up to ``jobs_per_round - 1``).
    ``donate=True`` lets XLA mutate the caller's state buffers in place —
    only for callers that own them exclusively (`SPFreshIndex.maintain`).
    ``access`` (optional probe histogram) folds into the FIRST round's
    selection; later rounds of the same drain see it via the state.
    ``force`` (optional postings to split first) steers the FIRST round's
    split slots, as in `maintenance_round`.
    Returns ``(state, jobs_done, rounds)``.
    """
    cfg = state.cfg
    jobs = int(jobs_per_round or cfg.jobs_per_round)
    cap_jobs = max_steps if max_steps is not None else 2 * cfg.num_postings_cap
    step = _donating_round(jobs) if donate else (
        lambda s, a, f: maintenance_round(s, jobs, a, f)
    )
    if access is not None:
        access = jnp.asarray(access, jnp.int32)
    if force is not None:
        force = jnp.asarray(force, jnp.int32)
    done = 0
    rounds = 0
    while done < cap_jobs:
        state, did = step(state, access, force)
        access = force = None
        rounds += 1
        d = int(did)  # the round's single device→host sync
        done += d
        if d == 0:
            break
    return state, done, rounds
