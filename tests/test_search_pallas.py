"""End-to-end parity: the Pallas paged posting scan (interpret mode) vs the
XLA gather oracle, both schedules, under inserts/deletes/splits.

The two data paths compute ``‖q−x‖²`` with different contraction layouts
(diff² gather vs per-page GEMM expansion), so distances can differ by the
f32 cancellation error of the expansion (~eps·‖q‖²).  On workloads whose
distance gaps resolve above that noise the top-k vids are identical; the
adversarial near-duplicate workload asserts the tie-tolerant contract
instead (any positional difference must be a sub-tolerance distance tie).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# check.sh runs this suite as its own explicit gate step; the tier-1
# step excludes it via the marker (no hand-maintained --ignore list).
pytestmark = pytest.mark.gate

from repro.core import lire
from repro.core.distance import MASK_DISTANCE
from repro.core.index import SPFreshIndex
from tests.conftest import make_clustered
from tests.test_lire import small_cfg

SCHEDULES = ("per_query", "batched")

_CACHE: dict = {}


def _churned_index(rng, *, near_dup=False, codec="fp32"):
    """Build + insert + delete + maintain: splits, stale replicas, GC'd
    postings, freed pages — every masking path the scan must honor.
    Built once per workload shape (fixed seed) and cached — the index is
    read-only in every test; tests that mutate copy the state first."""
    if (near_dup, codec) in _CACHE:
        return _CACHE[near_dup, codec]
    rng = np.random.default_rng(17 if near_dup else 7)
    base = make_clustered(rng, 900, 16, n_clusters=8)
    idx = SPFreshIndex.build(small_cfg(codec=codec), base)
    if near_dup:
        extra = (base[0][None, :] + 0.02 * rng.normal(size=(300, 16))
                 ).astype(np.float32)
    else:
        extra = make_clustered(rng, 250, 16, n_clusters=5)
    idx.insert(extra, np.arange(3000, 3000 + len(extra), dtype=np.int32))
    idx.delete(rng.choice(900, size=120, replace=False).astype(np.int32))
    idx.maintain()
    assert idx.stats()["n_splits"] > 0
    queries = np.concatenate([base[200:216], extra[:16]]) \
        + 0.01 * rng.normal(size=(32, 16)).astype(np.float32)
    _CACHE[near_dup, codec] = (idx, jnp.asarray(queries))
    return _CACHE[near_dup, codec]


def _assert_tie_tolerant(d0, v0, d1, v1, tol=1e-4):
    """Positions may differ only where the two paths report a distance tie
    within ``tol`` (f32 expansion noise); everything else is bit-equal."""
    np.testing.assert_allclose(d0, d1, atol=tol)
    mismatch = v0 != v1
    assert (np.abs(d0 - d1)[mismatch] < tol).all(), (
        v0[mismatch], v1[mismatch], d0[mismatch], d1[mismatch]
    )


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_search_parity_under_churn(rng, schedule):
    idx, queries = _churned_index(rng)
    d0, v0 = lire.search(idx.state, queries, k=10, nprobe=8)
    d1, v1 = lire.search(
        idx.state, queries, k=10, nprobe=8,
        use_pallas_scan=True, scan_schedule=schedule,
    )
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), atol=1e-4)


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_search_parity_near_duplicates(rng, schedule):
    """300 near-identical inserts: distance gaps at f32 resolution — the
    tie-tolerant contract is the strongest claim either path can make."""
    idx, queries = _churned_index(rng, near_dup=True)
    d0, v0 = lire.search(idx.state, queries, k=10, nprobe=8)
    d1, v1 = lire.search(
        idx.state, queries, k=10, nprobe=8,
        use_pallas_scan=True, scan_schedule=schedule,
    )
    _assert_tie_tolerant(
        np.asarray(d0), np.asarray(v0), np.asarray(d1), np.asarray(v1)
    )


def test_schedules_agree_with_each_other(rng):
    """Both Pallas schedules share kernel math → bit-identical results."""
    idx, queries = _churned_index(rng, near_dup=True)
    d1, v1 = lire.search(
        idx.state, queries, k=10, nprobe=8,
        use_pallas_scan=True, scan_schedule="per_query",
    )
    d2, v2 = lire.search(
        idx.state, queries, k=10, nprobe=8,
        use_pallas_scan=True, scan_schedule="batched",
    )
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_search_parity_respects_deletes(rng, schedule):
    """Deleted vids never surface through the paged scan."""
    cached, queries = _churned_index(rng)
    idx = SPFreshIndex(cached.state)  # jax state is immutable; cache intact
    victims = np.arange(200, 216, dtype=np.int32)
    idx.delete(victims)
    _, v1 = lire.search(
        idx.state, queries, k=10, nprobe=8,
        use_pallas_scan=True, scan_schedule=schedule,
    )
    assert not (set(victims.tolist()) & set(np.asarray(v1).reshape(-1).tolist()))


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_search_parity_config_flag(rng, schedule):
    """The LireConfig flags (not just the call-site override) select the
    Pallas path end-to-end through SPFreshIndex.search."""
    idx, queries = _churned_index(rng)
    d0, v0 = idx.search(np.asarray(queries), 10, nprobe=8)
    flagged = SPFreshIndex(idx.state.replace(cfg=dataclasses.replace(
        idx.state.cfg, use_pallas_scan=True, scan_schedule=schedule,
    )))
    d1, v1 = flagged.search(np.asarray(queries), 10, nprobe=8)
    np.testing.assert_array_equal(v0, v1)
    np.testing.assert_allclose(d0, d1, atol=1e-4)


def test_batched_page_budget_overflow_degrades_gracefully(rng):
    """A starved page budget drops pages (recall loss) but never produces
    duplicates, dead vids, or unsorted results."""
    idx, queries = _churned_index(rng)
    cfg = dataclasses.replace(idx.state.cfg, scan_page_budget=16)
    state = idx.state.replace(cfg=cfg)
    d1, v1 = lire.search(
        state, queries, k=10, nprobe=8,
        use_pallas_scan=True, scan_schedule="batched",
    )
    d1, v1 = np.asarray(d1), np.asarray(v1)
    for row_d, row_v in zip(d1, v1):
        valid = row_v >= 0
        ids = row_v[valid].tolist()
        assert len(ids) == len(set(ids))
        assert (np.diff(row_d[valid]) >= -1e-6).all()
    # a generous budget matches the oracle again
    cfg2 = dataclasses.replace(idx.state.cfg, scan_page_budget=4096)
    d2, v2 = lire.search(
        idx.state.replace(cfg=cfg2), queries, k=10, nprobe=8,
        use_pallas_scan=True, scan_schedule="batched",
    )
    d0, v0 = lire.search(idx.state, queries, k=10, nprobe=8)
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v2))


def _dedup_counts(state, queries, nprobe, budget, rows=None):
    """``dedup_pages`` over the batch's page table, outside ``search``:
    ``(distinct pages, pages over the budget, probed pages, table size,
    probed pages of the rows ``rows`` marks that the budget dropped)``."""
    from repro.kernels.posting_scan import ops as scan_ops

    rows = np.ones(queries.shape[0], bool) if rows is None else rows

    @jax.jit
    def f(state, queries, rows):
        nav_d, pids = lire.navigate(state, queries, nprobe)
        counted = (nav_d < MASK_DISTANCE / 2) & rows[:, None]
        flat = lire._page_table(state, pids, counted).reshape(-1)
        _, member_pos, n_unique, overflow = scan_ops.dedup_pages(
            flat, budget=budget, num_blocks=state.cfg.num_blocks)
        dropped = jnp.sum((flat >= 0) & (member_pos < 0))
        return n_unique, overflow, jnp.sum(flat >= 0), flat.size, dropped

    return [int(x) for x in f(state, queries, jnp.asarray(rows))]


def _page_counts(state, queries, **kw):
    *_, access = lire.search(state, queries, k=10, nprobe=8,
                             with_access=True, **kw)
    return lire.split_access(np.asarray(access))[1].tolist()


def test_search_page_counts_match_dedup_pages(rng):
    """``search(with_access=True)`` returns the page counts the batched
    scan's dedup computed; the other paths count every probed page."""
    idx, queries = _churned_index(rng)
    cfg = idx.state.cfg
    budget = min(queries.shape[0] * 8 * cfg.max_blocks_per_posting,
                 cfg.num_blocks)
    state = idx.state.replace(
        cfg=dataclasses.replace(cfg, scan_page_budget=budget))
    n_unique, overflow, probed, grid, _ = _dedup_counts(
        state, queries, 8, budget)
    assert overflow == 0 and 0 < n_unique < probed
    assert _page_counts(state, queries, use_pallas_scan=True,
                        scan_schedule="batched") == [
        n_unique, 0, budget, budget - n_unique]
    for kw in ({"use_pallas_scan": True, "scan_schedule": "per_query"}, {}):
        assert _page_counts(state, queries, **kw) == [probed, 0, grid, 0], kw


def test_search_page_counts_leave_padding_rows_out(rng):
    """Rows ``qvalid`` marks as padding probe pages of their own, which
    the scan streams but the page counts leave out."""
    idx, queries = _churned_index(rng)
    cfg = idx.state.cfg
    budget = min(queries.shape[0] * 8 * cfg.max_blocks_per_posting,
                 cfg.num_blocks)
    state = idx.state.replace(
        cfg=dataclasses.replace(cfg, scan_page_budget=budget))
    rows = np.arange(queries.shape[0]) < queries.shape[0] // 2
    n_all = _dedup_counts(state, queries, 8, budget)[0]
    n_unique, _, probed, grid, _ = _dedup_counts(state, queries, 8, budget,
                                                 rows)
    assert 0 < n_unique < n_all
    qv = jnp.asarray(rows)
    # the kernel still scans the padding rows' pages: only the steps past
    # all n_all distinct pages are skipped
    assert _page_counts(state, queries, qvalid=qv, use_pallas_scan=True,
                        scan_schedule="batched") == [
        n_unique, 0, budget, budget - n_all]
    for kw in ({"use_pallas_scan": True, "scan_schedule": "per_query"}, {}):
        assert _page_counts(state, queries, qvalid=qv, **kw) == [
            probed, 0, grid, 0], kw


def test_search_page_counts_show_dropped_pages(rng):
    """A starved page budget: the dispatch reports the probed pages it
    dropped, once per probing query."""
    idx, queries = _churned_index(rng)
    state = idx.state.replace(
        cfg=dataclasses.replace(idx.state.cfg, scan_page_budget=16))
    n_unique, overflow, _, _, dropped = _dedup_counts(state, queries, 8, 16)
    assert overflow > 0 and n_unique == 16 + overflow
    assert dropped >= overflow
    assert _page_counts(state, queries, use_pallas_scan=True,
                        scan_schedule="batched") == [16, dropped, 16, 0]


FIXED_GRID = 32_768


def _fixed_grid_candidates(state, queries, pids, probe_valid, counted, *,
                           k, schedule):
    """The batched scan as it ran with a fixed page grid: ``FIXED_GRID``
    dedup rows whatever the bucket, every grid step fetched and scored."""
    assert schedule == "batched"
    from repro.kernels.posting_scan import ops as scan_ops

    pool, budget = state.pool, FIXED_GRID
    q = pids.shape[0]
    mb, bs = pool.max_blocks_per_posting, pool.block_size
    kpage = min(k, bs)
    flat = lire._page_table(state, pids, probe_valid)
    safe_pp = jnp.maximum(jnp.repeat(pids, mb, axis=1), 0)
    uniq, member_pos, _, _ = scan_ops.dedup_pages(
        flat.reshape(-1), budget=budget, num_blocks=state.cfg.num_blocks)
    pages = lire._page_counts(flat, counted, member_pos, budget, budget)
    pvids, live = lire._page_slot_live(state, uniq)
    if pool.codec == "int8":
        tgt = jnp.where(member_pos >= 0, member_pos, budget)
        u_scale = jnp.ones((budget,), jnp.float32).at[tgt].set(
            pool.post_scale[safe_pp].reshape(-1), mode="drop")
        u_zero = jnp.zeros((budget,), jnp.float32).at[tgt].set(
            pool.post_zero[safe_pp].reshape(-1), mode="drop")
        d, slots = scan_ops.scan_unique_blocks_topk_q8(
            queries, uniq, live, pool.blocks, u_scale, u_zero, k=kpage)
    else:
        d, slots = scan_ops.scan_unique_blocks_topk(
            queries, uniq, live, pool.blocks, k=kpage)
    mp = member_pos.reshape(q, -1)
    safe_mp = jnp.maximum(mp, 0)
    qi = jnp.arange(q)[:, None]
    slot_q = slots[safe_mp, :, qi]
    cand_d = jnp.where((mp >= 0)[:, :, None], d[safe_mp, :, qi],
                       MASK_DISTANCE).reshape(q, -1)
    cand_v = jnp.take_along_axis(pvids[safe_mp], slot_q, axis=2).reshape(q, -1)
    cand_p = jnp.where((mp >= 0)[:, :, None],
                       uniq[safe_mp][:, :, None] * bs + slot_q, -1).reshape(q, -1)
    return cand_d, cand_v, cand_p, cand_d < MASK_DISTANCE / 2, pages


@functools.partial(jax.jit, static_argnums=0)
def _candidates(scan, state, queries, qvalid):
    """``scan``'s candidates for the batch ``search`` would hand it."""
    nav_d, pids = lire.navigate(state, queries, 8)
    probe_valid = nav_d < MASK_DISTANCE / 2
    return scan(state, queries, pids, probe_valid,
                probe_valid & qvalid[:, None], k=4, schedule="batched")


@pytest.mark.parametrize("codec", ["fp32", "int8"])
@pytest.mark.parametrize("bucket", [8, 16, 128])
def test_bounded_grid_matches_fixed_grid(rng, monkeypatch, bucket, codec):
    """The batched scan's grid bounded by the bucket (``Q·nprobe·MB`` pages
    at most) and stopped at the batch's distinct pages answers bit for
    bit as the fixed 32,768-page grid did, for 1..Q real rows with the
    rest zero padding; no page is dropped, and the counts report the
    grid steps past the distinct pages as skipped."""
    idx, pool_q = _churned_index(rng, codec=codec)
    state = idx.state.replace(cfg=dataclasses.replace(
        idx.state.cfg, use_pallas_scan=True, scan_schedule="batched",
        scan_page_budget=FIXED_GRID))
    cfg = state.cfg
    grid = min(bucket * 8 * cfg.max_blocks_per_posting, cfg.num_blocks)
    noise = np.random.default_rng(bucket).normal(size=(bucket, cfg.dim))
    rows = np.asarray(pool_q)[np.arange(bucket) % pool_q.shape[0]]
    rows = (rows + 0.05 * noise).astype(np.float32)
    search = functools.partial(lire.search, k=4, nprobe=8, with_access=True)
    bounded = jax.jit(search)
    for n_real in sorted({1, bucket // 2, bucket}):
        real = np.arange(bucket) < n_real
        queries = jnp.asarray(np.where(real[:, None], rows, 0.0))
        qvalid = jnp.asarray(real)
        # the candidates the reduce sees, then what it answers
        for got, want in zip(_candidates(lire._pallas_scan_candidates,
                                         state, queries, qvalid)[:3],
                             _candidates(_fixed_grid_candidates,
                                         state, queries, qvalid)[:3]):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        d1, v1, acc1 = bounded(state, queries, qvalid=qvalid)
        with monkeypatch.context() as m:
            m.setattr(lire, "_pallas_scan_candidates", _fixed_grid_candidates)
            d0, v0, acc0 = jax.jit(search)(state, queries, qvalid=qvalid)
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v0))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d0))
        kept, dropped, g, skipped = lire.split_access(np.asarray(acc1))[1]
        kept0, dropped0, _, _ = lire.split_access(np.asarray(acc0))[1]
        n_all = _dedup_counts(state, queries, 8, grid)[0]
        assert (kept, dropped, g) == (kept0, 0, grid) and dropped0 == 0
        assert skipped == grid - n_all > 0


@pytest.mark.parametrize("path", ["oracle", *SCHEDULES])
def test_search_with_access_keeps_answers_bit_identical(rng, path):
    idx, queries = _churned_index(rng)
    kw = {} if path == "oracle" else {"use_pallas_scan": True,
                                       "scan_schedule": path}
    d0, v0 = lire.search(idx.state, queries, k=10, nprobe=8, **kw)
    d1, v1, _ = lire.search(idx.state, queries, k=10, nprobe=8,
                            with_access=True, **kw)
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_grouped_search_pallas_parity(rng, schedule):
    from repro.core.grouping import build_group_index, search_grouped

    idx, queries = _churned_index(rng)
    gidx = build_group_index(idx.state, n_groups=8, capacity=64)
    d0, v0 = search_grouped(idx.state, gidx, queries, k=10, nprobe=8, gprobe=8)
    d1, v1 = search_grouped(
        idx.state, gidx, queries, k=10, nprobe=8, gprobe=8,
        use_pallas_scan=True, scan_schedule=schedule,
    )
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), atol=1e-4)


def test_grouped_search_probe_chunk_no_longer_dropped(rng):
    """search_grouped used to ignore probe_chunk; the shared reduce
    honors it (same results, chunked gather)."""
    from repro.core.grouping import build_group_index, search_grouped

    idx, queries = _churned_index(rng)
    gidx = build_group_index(idx.state, n_groups=8, capacity=64)
    d0, v0 = search_grouped(idx.state, gidx, queries, k=10, nprobe=8, gprobe=8)
    d1, v1 = search_grouped(
        idx.state, gidx, queries, k=10, nprobe=8, gprobe=8, probe_chunk=4,
    )
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), atol=1e-5)


def test_dedup_topk_matches_reference(rng):
    """The rewritten reduce (top_k prefilter + segment-min) must agree with
    the lexsort reference whenever prefilter covers the duplicates."""
    for trial in range(30):
        n = int(rng.integers(20, 400))
        k = int(rng.integers(1, 12))
        n_vids = max(2, n // int(rng.integers(1, 6)))
        vids = jnp.asarray(rng.integers(0, n_vids, size=n), jnp.int32)
        dists = jnp.asarray(rng.random(size=n), jnp.float32)
        live = jnp.asarray(rng.random(size=n) < 0.8)
        # pre-mask dead entries: the reference otherwise drops a vid whose
        # min-dist occurrence is dead (see _dedup_topk_1d_ref caveat)
        masked = jnp.where(live, dists, lire.MASK_DISTANCE)
        want_d, want_v = lire._dedup_topk_1d_ref(masked, vids, live, k)
        got_d, got_v = lire._dedup_topk_1d(dists, vids, live, k, n)
        np.testing.assert_array_equal(np.asarray(want_v), np.asarray(got_v))
        np.testing.assert_allclose(np.asarray(want_d), np.asarray(got_d))


def test_sharded_index_scan_flags(rng):
    """ShardedIndex threads the scan flags into its shard_map search step
    (1-shard mesh; tie-tolerant — shard_map changes contraction layout)."""
    import jax

    from repro.core.types import LireConfig
    from repro.distributed.sharded_index import ShardedIndex

    cfg = LireConfig(
        dim=16, block_size=8, max_blocks_per_posting=8, num_blocks=2048,
        num_postings_cap=256, num_vectors_cap=8192, split_limit=48,
        merge_limit=6, reassign_range=8, replica_count=2, nprobe=8,
    )
    base = make_clustered(rng, 800, 16, n_clusters=6)
    mesh = jax.make_mesh((1,), ("model",))
    idx0, _ = ShardedIndex.build(mesh, cfg, base, 1)
    idxp = ShardedIndex(
        mesh, cfg, idx0.stacked, 1,
        use_pallas_scan=True, scan_schedule="batched",
    )
    q = base[:16]
    d0, v0 = idx0.search(q, 10, 8)
    d1, v1 = idxp.search(q, 10, 8)
    _assert_tie_tolerant(d0, v0, d1, v1)


def test_engine_scan_knobs(rng):
    """EngineConfig scan knobs reach the search dispatch (results match a
    direct oracle search)."""
    from repro.serve.engine import EngineConfig, ServeEngine

    base = make_clustered(rng, 600, 16, n_clusters=6)
    idx = SPFreshIndex.build(small_cfg(), base)
    queries = base[:16]
    d0, v0 = idx.search(queries, 10)
    eng = ServeEngine(idx, EngineConfig(
        search_k=10, use_pallas_scan=True, scan_schedule="batched",
        probe_chunk=0,
    ))
    d1, v1 = eng.search(queries)
    np.testing.assert_array_equal(v0, v1)
    # probe_chunk knob on the oracle path
    eng2 = ServeEngine(SPFreshIndex(idx.state),
                       EngineConfig(search_k=10, probe_chunk=4))
    d2, v2 = eng2.search(queries)
    np.testing.assert_array_equal(v0, v2)
