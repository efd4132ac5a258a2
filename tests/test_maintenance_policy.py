"""Maintenance-policy ranking + per-posting telemetry unit/property tests.

Pins the PR's two contracts:

* ``policy="size"`` is BIT-IDENTICAL to the original top-K/bottom-K
  selection (regression pin vs an inline re-implementation), and a
  cold-start ``policy="drift"`` round (all-zero telemetry) produces
  bit-identical state leaves to the size round.
* The telemetry leaves obey conservation laws under split/merge/free
  (split halves carry the parent's access counts exactly; freed pids
  zero theirs) and the update counter tracks landed appends exactly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# check.sh runs this suite as its own explicit gate step; the tier-1
# step excludes it via the marker.
pytestmark = pytest.mark.gate

from repro.core import lire
from repro.core import types as T
from repro.core.index import SPFreshIndex, build_state
from repro.core.types import LireConfig


def small_cfg(**kw):
    args = dict(
        dim=16, block_size=8, max_blocks_per_posting=8, num_blocks=2048,
        num_postings_cap=256, num_vectors_cap=8192, split_limit=48,
        merge_limit=6, merge_fanout=4, reassign_range=8,
        reassign_budget=128, replica_count=2, nprobe=8, jobs_per_round=4,
    )
    args.update(kw)
    return LireConfig(**args)


def clustered(rng, n, dim=16, n_clusters=8):
    centers = rng.normal(size=(n_clusters, dim)) * 5
    return (
        centers[rng.integers(0, n_clusters, n)] + rng.normal(size=(n, dim))
    ).astype(np.float32)


def _churned_index(seed=3, policy="size", **cfg_kw):
    """A built index with enough hot-insert churn to create split and
    merge candidates."""
    rng = np.random.default_rng(seed)
    base = clustered(rng, 1000)
    idx = SPFreshIndex.build(small_cfg(maintain_policy=policy, **cfg_kw),
                             base)
    centroids = np.asarray(idx.state.centroids)[
        np.asarray(idx.state.centroid_valid)
    ]
    hot = np.concatenate([
        (c[None, :] + 0.05 * rng.normal(size=(40, 16))).astype(np.float32)
        for c in centroids[:4]
    ])
    idx.insert(hot, np.arange(4000, 4000 + len(hot), dtype=np.int32),
               max_retries=0)
    d = ((base - base[0]) ** 2).sum(-1)
    idx.delete(np.argsort(d)[:150].astype(np.int32))
    return idx


# ---------------------------------------------------------------------------
# policy="size" — regression pin against the original inline selection
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 4, 8])
def test_size_policy_reproduces_original_topk_bottomk(k):
    idx = _churned_index()
    state = idx.state
    split_pids, split_en, merge_pids, merge_en = lire._select_jobs(state, k)

    # the ORIGINAL selection, verbatim
    cfg = state.cfg
    lens = state.pool.posting_len
    valid = state.centroid_valid
    split_scores = jnp.where(valid, lens, -1)
    top_l, want_sp = jax.lax.top_k(split_scores, k)
    want_se = top_l > cfg.split_limit
    merge_scores = jnp.where(
        valid & (lens < cfg.merge_limit), lens, jnp.iinfo(jnp.int32).max
    )
    neg_l, want_mp = jax.lax.top_k(-merge_scores, k)
    want_me = (-neg_l) < cfg.merge_limit

    np.testing.assert_array_equal(np.asarray(split_pids), np.asarray(want_sp))
    np.testing.assert_array_equal(np.asarray(split_en), np.asarray(want_se))
    np.testing.assert_array_equal(np.asarray(merge_pids), np.asarray(want_mp))
    np.testing.assert_array_equal(np.asarray(merge_en), np.asarray(want_me))
    assert bool(np.asarray(split_en).any()), "fixture produced no splits"
    assert bool(np.asarray(merge_en).any()), "fixture produced no merges"


def test_size_policy_ignores_telemetry():
    """Size selection must not read the telemetry leaves at all."""
    idx = _churned_index()
    state = idx.state
    tel = state.telemetry
    noisy = state.replace(telemetry=tel.replace(
        access_count=tel.access_count + 1000,
        update_count=tel.update_count + 7,
    ))
    for a, b in zip(lire._select_jobs(state, 4),
                    lire._select_jobs(noisy, 4)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# cold start: drift with all-zero telemetry == size, bit-exactly
# ---------------------------------------------------------------------------

def test_drift_cold_start_round_is_bit_identical_to_size():
    """With zero telemetry the drift formulas are monotone in length, so
    a whole maintenance_round produces bit-identical state leaves.

    The fixture builds OVERSIZED postings directly (build_posting_size >
    split_limit) and deletes a cluster for merge candidates — inserts
    would bump update/drift telemetry and leave cold-start territory."""
    rng = np.random.default_rng(9)
    base = clustered(rng, 1500)
    cfg_size = small_cfg(maintain_policy="size")
    state = build_state(cfg_size, base, build_posting_size=60)
    d = ((base - base[0]) ** 2).sum(-1)
    state = lire.delete_batch(
        state, jnp.asarray(np.argsort(d)[:256].astype(np.int32)),
        jnp.ones(256, bool),
    )
    assert int(np.asarray(state.telemetry.access_count).sum()) == 0
    assert int(np.asarray(state.telemetry.update_count).sum()) == 0

    cfg_drift = small_cfg(maintain_policy="drift", maintain_alpha=4.0,
                          maintain_beta=2.0)
    out_size, did_size = lire.maintenance_round(state, 4)
    out_drift, did_drift = lire.maintenance_round(
        state.replace(cfg=cfg_drift), 4
    )
    assert int(did_size) == int(did_drift) > 0
    for a, b in zip(jax.tree_util.tree_leaves(out_size),
                    jax.tree_util.tree_leaves(out_drift)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# drift ranking: access boost + drift term change the order
# ---------------------------------------------------------------------------

def _two_oversized(seed=13):
    """A state with ≥2 oversized postings; returns (state, long_pid,
    short_pid) where long > short in length, both split-eligible."""
    rng = np.random.default_rng(seed)
    base = clustered(rng, 1500)
    state = build_state(small_cfg(), base, build_posting_size=60)
    lens = np.asarray(state.pool.posting_len)
    valid = np.asarray(state.centroid_valid)
    eligible = np.flatnonzero(valid & (lens > state.cfg.split_limit))
    assert len(eligible) >= 2, "fixture needs 2+ oversized postings"
    order = eligible[np.argsort(-lens[eligible], kind="stable")]
    return state, int(order[0]), int(order[-1])


def test_drift_access_boost_beats_length_with_k1():
    state, long_pid, short_pid = _two_oversized()
    cfg = small_cfg(maintain_policy="drift", maintain_alpha=8.0,
                    maintain_beta=0.0)
    state = state.replace(cfg=cfg)

    # no access: drift degrades to size ordering -> the longest wins
    sp, se, _, _ = lire._select_jobs(state, 1)
    assert bool(np.asarray(se)[0])
    assert int(np.asarray(sp)[0]) == long_pid

    # all probes hit the SHORT oversized posting -> it outranks
    acc = np.zeros(cfg.num_postings_cap, np.int32)
    acc[short_pid] = 500
    hot = state.replace(telemetry=state.telemetry.replace(
        access_count=jnp.asarray(acc)
    ))
    sp, se, _, _ = lire._select_jobs(hot, 1)
    assert bool(np.asarray(se)[0])
    assert int(np.asarray(sp)[0]) == short_pid


def test_drift_term_prioritizes_drifted_posting():
    state, long_pid, short_pid = _two_oversized()
    cfg = small_cfg(maintain_policy="drift", maintain_alpha=0.0,
                    maintain_beta=50.0)
    state = state.replace(cfg=cfg)
    # the short posting's appends drifted far from its centroid
    drift = np.zeros((cfg.num_postings_cap, cfg.dim), np.float32)
    drift[short_pid] = 40.0
    upd = np.zeros(cfg.num_postings_cap, np.int32)
    upd[short_pid] = 4
    moved = state.replace(telemetry=state.telemetry.replace(
        drift_vec=jnp.asarray(drift), update_count=jnp.asarray(upd)
    ))
    sp, se, _, _ = lire._select_jobs(moved, 1)
    assert bool(np.asarray(se)[0])
    assert int(np.asarray(sp)[0]) == short_pid


def test_drift_merge_keeps_hot_runts_last():
    """Among mergeable runts of EQUAL length, accessed ones rank later
    (merged last) under the drift policy."""
    rng = np.random.default_rng(21)
    base = clustered(rng, 300)
    # build with tiny postings -> EVERY posting is a merge candidate
    state = build_state(small_cfg(), base, build_posting_size=3)
    lens = np.asarray(state.pool.posting_len)
    valid = np.asarray(state.centroid_valid)
    runts = np.flatnonzero(valid & (lens < state.cfg.merge_limit)
                           & (lens > 0))
    assert len(runts) >= 2, "fixture needs 2+ mergeable runts"
    # the size tie-break would merge the lowest-index runt first; heat it
    a = int(runts[0])
    acc = np.zeros(state.cfg.num_postings_cap, np.int32)
    acc[a] = 100
    cfg = small_cfg(maintain_policy="drift", maintain_alpha=8.0)
    hot = state.replace(cfg=cfg, telemetry=state.telemetry.replace(
        access_count=jnp.asarray(acc)
    ))
    _, _, mp, me = lire._select_jobs(hot, 1)
    assert bool(np.asarray(me)[0])
    assert int(np.asarray(mp)[0]) != a, "hot runt merged first"


# ---------------------------------------------------------------------------
# K edge cases
# ---------------------------------------------------------------------------

def test_jobs_per_round_zero_defers_to_cfg():
    """jobs_per_round=0 is falsy -> cfg.jobs_per_round, and huge K is
    clamped to num_postings_cap // 2 — both pin the `max(1, min(...))`
    behavior."""
    idx = _churned_index()
    s0, did0 = lire.maintenance_round(idx.state, 0)
    s_cfg, did_cfg = lire.maintenance_round(
        idx.state, idx.state.cfg.jobs_per_round
    )
    assert int(did0) == int(did_cfg)
    for a, b in zip(jax.tree_util.tree_leaves(s0),
                    jax.tree_util.tree_leaves(s_cfg)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # huge K clamps instead of erroring
    _, did_huge = lire.maintenance_round(
        idx.state, 10 * idx.state.cfg.num_postings_cap
    )
    assert int(did_huge) >= int(did_cfg)


def test_all_ties_pick_lowest_indices_under_both_policies():
    """All-equal lengths (and zero telemetry): both policies must pick
    the same lowest-index pids — top_k's documented tie-breaking."""
    rng = np.random.default_rng(17)
    base = clustered(rng, 1200)
    state = build_state(small_cfg(), base, build_posting_size=60)
    lens = np.asarray(state.pool.posting_len)
    valid = np.asarray(state.centroid_valid)
    tied = np.flatnonzero(valid & (lens == lens[valid].max()))
    k = min(3, len(tied))
    sel_size = lire._select_jobs(state, k)
    sel_drift = lire._select_jobs(
        state.replace(cfg=small_cfg(maintain_policy="drift")), k
    )
    for a, b in zip(sel_size, sel_drift):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# telemetry semantics: search histogram, conservation, zeroing
# ---------------------------------------------------------------------------

def test_search_probe_histogram_counts_and_qvalid_mask():
    idx = _churned_index()
    state = idx.state
    rng = np.random.default_rng(1)
    q = rng.normal(size=(8, 16)).astype(np.float32)
    d, v, acc = lire.search(state, jnp.asarray(q), k=10, nprobe=4,
                            with_access=True)
    hist, _ = lire.split_access(np.asarray(acc))
    assert hist.shape == (state.cfg.num_postings_cap,)
    assert hist.sum() == 8 * 4, "every (query, probe) counted once"
    assert (hist[~np.asarray(state.centroid_valid)] == 0).all()

    # qvalid masks padded rows out of the HISTOGRAM only
    qv = np.zeros(8, bool)
    qv[:3] = True
    d2, v2, acc2 = lire.search(state, jnp.asarray(q), k=10, nprobe=4,
                               with_access=True, qvalid=jnp.asarray(qv))
    hist2, _ = lire.split_access(np.asarray(acc2))
    assert hist2.sum() == 3 * 4
    np.testing.assert_array_equal(np.asarray(d2), np.asarray(d))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v))

    # with_access=False returns the original 2-tuple, bit-identical
    d3, v3 = lire.search(state, jnp.asarray(q), k=10, nprobe=4)
    np.testing.assert_array_equal(np.asarray(d3), np.asarray(d))
    np.testing.assert_array_equal(np.asarray(v3), np.asarray(v))


def test_update_count_tracks_landed_appends_exactly():
    rng = np.random.default_rng(2)
    base = clustered(rng, 800)
    idx = SPFreshIndex.build(small_cfg(), base)
    s0 = idx.state
    appends0 = int(s0.stats.n_appends)
    assert int(np.asarray(s0.telemetry.update_count).sum()) == 0

    vecs = clustered(rng, 120)
    idx.insert(vecs, np.arange(4000, 4120, dtype=np.int32), max_retries=0)
    s1 = idx.state
    d_appends = int(s1.stats.n_appends) - appends0
    assert d_appends > 0
    assert int(np.asarray(s1.telemetry.update_count).sum()) == d_appends


def test_split_conserves_access_and_freed_pids_zero():
    """Run drift-policy rounds with folded access over a churned state:
    split halves carry the parent's counts exactly (total conserved when
    merges are disabled), and invalid pids hold zero telemetry."""
    idx = _churned_index(policy="drift", enable_merge=False)
    state = idx.state
    cap = state.cfg.num_postings_cap
    rng = np.random.default_rng(4)
    access = rng.integers(0, 50, size=cap).astype(np.int32)
    access[~np.asarray(state.centroid_valid)] = 0
    total = int(np.asarray(state.telemetry.access_count).sum()
                + access.sum())
    out, did = lire.maintenance_round(state, 4, jnp.asarray(access))
    assert int(did) > 0
    out_acc = np.asarray(out.telemetry.access_count)
    valid = np.asarray(out.centroid_valid)
    assert int(out_acc.sum()) == total, "split did not conserve access"
    assert (out_acc[~valid] == 0).all()
    assert (np.asarray(out.telemetry.update_count)[~valid] == 0).all()
    assert (np.asarray(out.telemetry.drift_vec)[~valid] == 0).all()


def test_merge_moves_access_to_target_and_zeroes_source():
    idx = _churned_index(policy="drift")
    state = idx.state
    lens = np.asarray(state.pool.posting_len)
    valid = np.asarray(state.centroid_valid)
    runts = np.flatnonzero(valid & (lens < state.cfg.merge_limit)
                           & (lens > 0))
    assert len(runts) >= 1
    cap = state.cfg.num_postings_cap
    access = np.zeros(cap, np.int32)
    access[runts[0]] = 77
    before = int(np.asarray(state.telemetry.access_count).sum()) + 77
    out, did = lire.maintenance_round(state, 4, jnp.asarray(access))
    assert int(did) > 0
    out_acc = np.asarray(out.telemetry.access_count)
    out_valid = np.asarray(out.centroid_valid)
    if not out_valid[runts[0]]:
        # the runt merged away: its counts moved to the absorb target
        # (total conserved up to split-free/retire bookkeeping)
        assert out_acc[runts[0]] == 0
    assert (out_acc[~out_valid] == 0).all()
    assert int(out_acc.sum()) <= before


def test_telemetry_conservation_property():
    """Hypothesis: random churn + drift rounds — invalid pids always hold
    zero telemetry and valid access never exceeds what was folded in."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    cfg = small_cfg(
        dim=8, num_postings_cap=128, num_blocks=1024, num_vectors_cap=2048,
        split_limit=24, merge_limit=4, reassign_range=4, reassign_budget=64,
        maintain_policy="drift", maintain_alpha=2.0,
    )

    @settings(max_examples=4, deadline=None)
    @given(data=st.data())
    def inner(data):
        rng = np.random.default_rng(data.draw(st.integers(0, 10_000)))
        base = rng.normal(size=(300, 8)).astype(np.float32)
        idx = SPFreshIndex.build(cfg, base)
        next_vid = 300
        folded = 0
        for _ in range(data.draw(st.integers(1, 3))):
            k = data.draw(st.integers(1, 40))
            if data.draw(st.booleans()):
                c = base[data.draw(st.integers(0, 299))]
                vecs = (c[None] + 0.05 * rng.normal(size=(k, 8))
                        ).astype(np.float32)
            else:
                vecs = rng.normal(size=(k, 8)).astype(np.float32)
            idx.insert(vecs, np.arange(next_vid, next_vid + k,
                                       dtype=np.int32), max_retries=0)
            next_vid += k
            access = rng.integers(0, 20, size=cfg.num_postings_cap
                                  ).astype(np.int32)
            access[~np.asarray(idx.state.centroid_valid)] = 0
            folded += int(access.sum())
            idx.maintain_round(data.draw(st.sampled_from([1, 4])),
                               access=access)
            s = idx.state
            valid = np.asarray(s.centroid_valid)
            acc = np.asarray(s.telemetry.access_count)
            upd = np.asarray(s.telemetry.update_count)
            dv = np.asarray(s.telemetry.drift_vec)
            assert (acc >= 0).all()
            assert (acc[~valid] == 0).all()
            assert (upd[~valid] == 0).all()
            assert (dv[~valid] == 0).all()
            assert int(acc.sum()) <= folded, "access appeared from nowhere"

    inner()


def test_spec_threads_policy_into_config():
    import spfresh

    spec = spfresh.ServiceSpec(
        index=spfresh.IndexSpec(config=small_cfg()),
        maintenance=spfresh.MaintenanceSpec(
            policy="drift", alpha=2.5, beta=0.5
        ),
    )
    cfg = spec.lire_config()
    assert cfg.maintain_policy == "drift"
    assert cfg.maintain_alpha == 2.5
    assert cfg.maintain_beta == 0.5
    # None defers to IndexSpec.config
    spec2 = spfresh.ServiceSpec(index=spfresh.IndexSpec(config=small_cfg()))
    assert spec2.lire_config() == small_cfg()
    with pytest.raises(AssertionError):
        spfresh.ServiceSpec(
            index=spfresh.IndexSpec(config=small_cfg()),
            maintenance=spfresh.MaintenanceSpec(policy="sizzle"),
        ).validate()
