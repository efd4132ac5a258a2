"""Posting payload codec: quantization round-trip properties, dequant
kernel parity, pool-tier invariants, legacy-snapshot migration, and the
int8+rerank recall-floor gate.

check.sh runs this suite as its own explicit gate step; tier-1 excludes
it via the marker.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.gate

from repro.core.index import SPFreshIndex
from repro.core.types import LireConfig
from repro.data.vectors import make_sift_like
from repro.kernels.posting_scan import ops as scan_ops
from repro.kernels.posting_scan.kernel import (
    scan_batched_topk_q8,
    scan_per_query_topk_q8,
)
from repro.kernels.posting_scan.ref import (
    scan_batched_topk_q8_ref,
    scan_per_query_topk_q8_ref,
)
from repro.storage import blockpool as bp
from repro.storage import codec as pc


# ---------------------------------------------------------------------------
# Round-trip properties
# ---------------------------------------------------------------------------

def _roundtrip_bound(rows: np.ndarray, mag: float = 1.0) -> None:
    """decode(encode(x)) is within scale/2 per dimension (+fp32 slack)."""
    scale, zero = pc.np_train_scale_zero(rows)
    dec = pc.np_decode(pc.np_encode(rows, scale, zero), scale, zero)
    bound = float(scale) * 0.5 * (1 + 1e-3) + 1e-5 * max(mag, 1.0)
    assert np.max(np.abs(dec - rows)) <= bound, (scale, mag)


def test_roundtrip_error_bound_hypothesis():
    """Property form: the bound holds at any posting size, dim, and
    scale magnitude (outlier postings just get a larger scale)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=40, deadline=None)
    @given(
        n=st.integers(1, 12),
        d=st.sampled_from([4, 8, 16]),
        mag=st.floats(1e-3, 1e6),
        seed=st.integers(0, 2**31 - 1),
    )
    def inner(n, d, mag, seed):
        rng = np.random.default_rng(seed)
        rows = (mag * rng.normal(size=(n, d))).astype(np.float32)
        _roundtrip_bound(rows, mag)

    inner()


def test_roundtrip_error_bound_seeded():
    """Deterministic trials that run even without hypothesis, covering
    the same envelope: sizes, dims, and outlier scale magnitudes."""
    for seed in range(20):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 13))
        d = int(rng.choice([4, 8, 16]))
        mag = float(10.0 ** rng.uniform(-3, 6))
        rows = (mag * rng.normal(size=(n, d))).astype(np.float32)
        _roundtrip_bound(rows, mag)


def test_all_zero_posting_roundtrips_exactly():
    for n, d in ((1, 4), (8, 16)):
        rows = np.zeros((n, d), np.float32)
        scale, zero = pc.np_train_scale_zero(rows)
        assert scale == 1.0 and zero == 0.0
        dec = pc.np_decode(pc.np_encode(rows, scale, zero), scale, zero)
        np.testing.assert_array_equal(dec, rows)


def test_single_vector_posting_bound():
    for seed in range(10):
        rng = np.random.default_rng(seed)
        rows = rng.normal(size=(1, int(rng.choice([4, 8, 16])))) \
            .astype(np.float32)
        _roundtrip_bound(rows)


def test_constant_posting_roundtrips_exactly():
    rows = np.full((5, 8), 3.25, np.float32)
    scale, zero = pc.np_train_scale_zero(rows)
    assert scale == 1.0 and zero == np.float32(3.25)
    dec = pc.np_decode(pc.np_encode(rows, scale, zero), scale, zero)
    np.testing.assert_array_equal(dec, rows)


def test_jnp_train_matches_np_train():
    """The traced trainer (masked, batched) agrees with the host one."""
    for seed in range(10):
        rng = np.random.default_rng(seed)
        n, d = 6, 8
        rows = (10.0 * rng.normal(size=(n, d))).astype(np.float32)
        n_valid = int(rng.integers(1, n + 1))
        valid = np.arange(n) < n_valid
        s_j, z_j = pc.train_scale_zero(jnp.asarray(rows), jnp.asarray(valid))
        s_n, z_n = pc.np_train_scale_zero(rows[:n_valid])
        np.testing.assert_allclose(float(s_j), float(s_n), rtol=1e-6)
        np.testing.assert_allclose(
            float(z_j), float(z_n), rtol=1e-6, atol=1e-6
        )


# ---------------------------------------------------------------------------
# Dequant-fused kernel parity (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q_n,n_blocks,bs,d,nb,k", [
    (4, 32, 8, 16, 6, 4),
    (2, 16, 8, 32, 3, 8),
])
def test_q8_per_query_topk_matches_ref(rng, q_n, n_blocks, bs, d, nb, k):
    blocks = jnp.asarray(
        rng.integers(-127, 128, size=(n_blocks, bs, d)), jnp.int8
    )
    queries = jnp.asarray(rng.normal(size=(q_n, d)), jnp.float32)
    table = jnp.asarray(rng.integers(0, n_blocks, size=(q_n, nb)), jnp.int32)
    bias = jnp.zeros((q_n, nb, bs), jnp.float32)
    page_sz = jnp.asarray(
        np.stack(
            [rng.uniform(1e-3, 0.1, size=(q_n, nb)),
             rng.normal(size=(q_n, nb))], axis=-1
        ), jnp.float32,
    )
    got_d, got_i = scan_per_query_topk_q8(
        table, queries, blocks, bias, page_sz, k=k, interpret=True
    )
    want_d, want_i = scan_per_query_topk_q8_ref(
        table, queries, blocks, bias, page_sz, k=k
    )
    np.testing.assert_allclose(
        np.asarray(got_d), np.asarray(want_d), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


@pytest.mark.parametrize("q_n,n_blocks,bs,d,nb,k", [
    (4, 32, 8, 16, 6, 4),
    (8, 64, 16, 128, 5, 8),
])
def test_q8_batched_topk_matches_ref(rng, q_n, n_blocks, bs, d, nb, k):
    blocks = jnp.asarray(
        rng.integers(-127, 128, size=(n_blocks, bs, d)), jnp.int8
    )
    queries = jnp.asarray(rng.normal(size=(q_n, d)), jnp.float32)
    ids = jnp.asarray(rng.choice(n_blocks, size=nb, replace=False), jnp.int32)
    bias = jnp.zeros((nb, bs), jnp.float32)
    page_sz = jnp.asarray(
        np.stack(
            [rng.uniform(1e-3, 0.1, size=(nb,)),
             rng.normal(size=(nb,))], axis=-1
        ), jnp.float32,
    )
    got_d, got_i = scan_batched_topk_q8(
        ids, queries, blocks, bias, page_sz, k=k, interpret=True
    )
    want_d, want_i = scan_batched_topk_q8_ref(
        ids, queries, blocks, bias, page_sz, k=k
    )
    np.testing.assert_allclose(
        np.asarray(got_d), np.asarray(want_d), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def test_q8_wrapper_equals_dequantized_fp32_wrapper(rng):
    """The q8 ops wrapper over codes == the fp32 wrapper over the
    decoded payload (same pages, same bias) — the dequant really is the
    only difference in the data path."""
    n_blocks, bs, d, q_n, nb, k = 16, 8, 16, 3, 4, 4
    scale = rng.uniform(1e-3, 0.05, size=(q_n, nb)).astype(np.float32)
    zero = rng.normal(size=(q_n, nb)).astype(np.float32)
    codes = rng.integers(-127, 128, size=(n_blocks, bs, d)).astype(np.int8)
    queries = jnp.asarray(rng.normal(size=(q_n, d)), jnp.float32)
    table = jnp.asarray(rng.integers(0, n_blocks, size=(q_n, nb)), jnp.int32)
    live = jnp.ones((q_n, nb, bs), bool)
    got_d, _ = scan_ops.scan_posting_blocks_topk_q8(
        queries, table, live, jnp.asarray(codes),
        jnp.asarray(scale), jnp.asarray(zero), k=k, interpret=True,
    )
    # decode each probed page under ITS page's params, then fp32-scan
    dec = np.zeros((q_n, nb, bs, d), np.float32)
    for q in range(q_n):
        for j in range(nb):
            dec[q, j] = pc.np_decode(
                codes[np.asarray(table)[q, j]], scale[q, j], zero[q, j]
            )
    diff = dec - np.asarray(queries)[:, None, None, :]
    dist = (diff * diff).sum(-1)
    want_d = np.sort(dist.reshape(q_n, nb, bs), axis=-1)[..., :k]
    np.testing.assert_allclose(
        np.sort(np.asarray(got_d), axis=-1), want_d, rtol=1e-4, atol=1e-4
    )


# ---------------------------------------------------------------------------
# Pool tiers
# ---------------------------------------------------------------------------

def _int8_pool(dim=8, cap=4):
    return bp.make_block_pool(
        num_blocks=32, block_size=4, dim=dim, num_postings_cap=8,
        max_blocks_per_posting=cap, codec="int8",
    )


def _put(pool, pid, vecs):
    """put_posting with fixed-capacity padding around (n, d) rows."""
    cap = pool.posting_capacity
    n = vecs.shape[0]
    buf = np.zeros((cap, pool.dim), np.float32)
    buf[:n] = vecs
    vids = np.full((cap,), -1, np.int32)
    vids[:n] = np.arange(n)
    return bp.put_posting(
        pool, jnp.int32(pid), jnp.asarray(buf), jnp.asarray(vids),
        jnp.zeros((cap,), pool.block_ver.dtype), jnp.int32(n),
        jnp.bool_(True),
    )


def test_int8_pool_put_roundtrip_and_exact_tier(rng):
    pool = _int8_pool()
    vecs = rng.normal(size=(12, 8)).astype(np.float32)
    pool, ok = _put(pool, 2, vecs)
    assert bool(ok)
    exact, _, _, valid = bp.gather_posting(pool, 2)
    assert int(np.asarray(valid).sum()) == 12
    # cold tier is EXACT fp32
    np.testing.assert_array_equal(np.asarray(exact)[:12], vecs)
    # hot tier decodes within the posting's quantization bound
    hot, _, _, _ = bp.gather_posting_hot(pool, 2)
    bound = float(pool.post_scale[2]) * 0.5 * (1 + 1e-3)
    assert np.max(np.abs(np.asarray(hot)[:12] - vecs)) <= bound


def test_int8_pool_free_resets_codec_params(rng):
    pool = _int8_pool()
    vecs = rng.normal(size=(4, 8)).astype(np.float32)
    pool, ok = _put(pool, 1, vecs)
    assert bool(ok)
    assert float(pool.post_scale[1]) != 1.0
    pool = bp.free_posting(pool, jnp.int32(1), jnp.bool_(True))
    assert float(pool.post_scale[1]) == 1.0
    assert float(pool.post_zero[1]) == 0.0


def test_fp32_pool_has_no_exact_tier():
    pool = bp.make_block_pool(
        num_blocks=16, block_size=4, dim=8, num_postings_cap=4,
        max_blocks_per_posting=2, codec="fp32",
    )
    assert pool.blocks_exact is None
    assert pool.blocks.dtype == jnp.float32


# ---------------------------------------------------------------------------
# Legacy snapshot migration + replay-drift rejection
# ---------------------------------------------------------------------------

def _tiny_cfg(**kw):
    args = dict(
        dim=8, block_size=4, max_blocks_per_posting=4, num_blocks=256,
        num_postings_cap=64, num_vectors_cap=1024, split_limit=12,
        merge_limit=2, reassign_range=4, reassign_budget=32,
        replica_count=1, nprobe=4,
    )
    args.update(kw)
    return LireConfig(**args)


def test_pre_codec_snapshot_migrates(tmp_path, rng):
    """A snapshot written before the codec leaves existed loads as fp32
    with identity codec params reconstructed (scale=1, zero=0)."""
    import jax
    from repro.storage import snapshot as snap

    base = make_sift_like(200, 8, seed=3)
    idx = SPFreshIndex.build(_tiny_cfg(), base)
    state = idx.state
    leaves = jax.tree_util.tree_leaves(state)
    codec_at = snap._codec_leaf_indices(state)
    assert len(codec_at) == 2
    drop = set(codec_at.values())
    kept = [np.asarray(x) for i, x in enumerate(leaves) if i not in drop]
    path = os.path.join(tmp_path, "snap")
    os.makedirs(path)
    np.savez(
        os.path.join(path, "leaves.npz"),
        **{f"leaf_{i}": a for i, a in enumerate(kept)},
    )
    with open(os.path.join(path, "manifest.json"), "w") as fh:
        json.dump({"format": 2, "kind": "base", "n_leaves": len(kept),
                   "step": 0, "extra": {}}, fh)
    restored, _ = snap.load_snapshot(path, state)
    np.testing.assert_array_equal(
        np.asarray(restored.pool.post_scale),
        np.ones_like(np.asarray(state.pool.post_scale)),
    )
    np.testing.assert_array_equal(
        np.asarray(restored.pool.post_zero),
        np.zeros_like(np.asarray(state.pool.post_zero)),
    )
    np.testing.assert_array_equal(
        np.asarray(restored.pool.blocks), np.asarray(state.pool.blocks)
    )


def test_pre_codec_delta_chain_folds_then_migrates(tmp_path, rng):
    """A base+delta chain written before the codec leaves existed must
    fold in ITS OWN leaf coordinates (the deltas stamp old indices) and
    migrate once at the end."""
    import jax
    from repro.storage import snapshot as snap

    base_vecs = make_sift_like(200, 8, seed=5)
    idx = SPFreshIndex.build(_tiny_cfg(), base_vecs)
    state = idx.state
    leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(state)]
    drop = sorted(snap._codec_leaf_indices(state).values())
    old = [a for i, a in enumerate(leaves) if i not in drop]
    # new-coordinate block leaf indices -> old-coordinate ones
    blk_new = snap._block_leaf_indices(state)
    to_old = lambda i: i - sum(1 for d in drop if d < i)
    blk_old = {name: to_old(i) for name, i in blk_new.items()}

    root = os.path.join(tmp_path, "store")
    bdir = os.path.join(root, "base-0000000001")
    os.makedirs(bdir)
    np.savez(os.path.join(bdir, "leaves.npz"),
             **{f"leaf_{i}": a for i, a in enumerate(old)})
    with open(os.path.join(bdir, "manifest.json"), "w") as fh:
        json.dump({"format": 2, "kind": "base", "unit": "base-0000000001",
                   "parent": None, "chain_len": 0, "n_leaves": len(old),
                   "step": 0, "extra": {}}, fh)

    # delta touching one block, everything in OLD coordinates
    bid = 0
    new_page = rng.normal(size=old[blk_old["blocks"]].shape[1:]) \
        .astype(old[blk_old["blocks"]].dtype)
    ddir = os.path.join(root, "delta-0000000002")
    os.makedirs(ddir)
    arrays = {"dirty_idx": np.asarray([bid], np.int32)}
    for name in ("blocks", "block_vid", "block_ver"):
        rowval = new_page[None] if name == "blocks" \
            else old[blk_old[name]][bid:bid + 1]
        arrays[f"blk_{name}"] = rowval
    blk_idx = set(blk_old.values())
    for j, a in enumerate(old):
        if j not in blk_idx:
            arrays[f"leaf_{j}"] = a
    np.savez(os.path.join(ddir, "shard_000.npz"), **arrays)
    with open(os.path.join(ddir, "manifest.json"), "w") as fh:
        json.dump({"format": 2, "kind": "delta", "unit": "delta-0000000002",
                   "parent": "base-0000000001", "chain_len": 1,
                   "n_leaves": len(old), "n_shards": 1,
                   "block_leaves": blk_old, "step": 0, "extra": {}}, fh)
    with open(os.path.join(root, "CURRENT"), "w") as fh:
        fh.write("delta-0000000002")

    restored, _ = snap.SnapshotStore(root).load(state)
    np.testing.assert_array_equal(
        np.asarray(restored.pool.blocks)[bid], new_page
    )
    np.testing.assert_array_equal(
        np.asarray(restored.pool.post_scale),
        np.ones_like(np.asarray(state.pool.post_scale)),
    )


def test_replay_rejects_codec_drift():
    from repro.storage.durability import check_replay_config

    cfg = _tiny_cfg(codec="int8", rerank_factor=4)
    stamped_fp32 = {"extra": {"lire_config": {"codec": "fp32",
                                              "rerank_factor": 1}}}
    with pytest.raises(ValueError, match="codec"):
        check_replay_config(stamped_fp32, cfg)
    # pre-codec snapshots never stamped the field -> they still pass
    legacy = {"extra": {"lire_config": {"dim": cfg.dim}}}
    check_replay_config(legacy, cfg)


def test_replay_ignores_the_platform_interpret_stamp():
    """Snapshots once stamped whether the kernels ran interpreted; that is
    the platform's choice now, so a snapshot written on the CPU reopens
    on a TPU — while a real geometry drift is still refused."""
    import dataclasses

    from repro.storage.durability import check_replay_config

    cfg = _tiny_cfg()
    stamped = dataclasses.asdict(cfg)
    stamped["pallas_interpret"] = True
    check_replay_config({"extra": {"lire_config": stamped}}, cfg)
    stamped["dim"] = cfg.dim + 1
    with pytest.raises(ValueError, match="dim"):
        check_replay_config({"extra": {"lire_config": stamped}}, cfg)


# ---------------------------------------------------------------------------
# Recall-floor gate: int8 + exact rerank within 0.01 recall@10 of fp32
# ---------------------------------------------------------------------------

def _recall_cell(codec: str, rerank_factor: int) -> float:
    n, dim, k = 600, 16, 10
    base = make_sift_like(n, dim, seed=41)
    cfg = _tiny_cfg(
        dim=dim, num_blocks=1024, num_postings_cap=128,
        num_vectors_cap=4096, codec=codec, rerank_factor=rerank_factor,
    )
    idx = SPFreshIndex.build(cfg, base)
    rng = np.random.default_rng(42)
    queries = (base[rng.integers(0, n, 24)]
               + 0.02 * rng.normal(size=(24, dim))).astype(np.float32)
    d = ((queries[:, None, :] - base[None]) ** 2).sum(-1)
    gt = np.argsort(d, axis=1)[:, :k]
    _, got = idx.search(queries, k, nprobe=8)
    hits = sum(
        len(set(a.tolist()) & set(b.tolist())) for a, b in zip(gt, got)
    )
    return hits / gt.size


def test_int8_rerank_recall_floor():
    r_fp32 = _recall_cell("fp32", 1)
    r_int8 = _recall_cell("int8", 4)
    assert r_fp32 - r_int8 <= 0.01, (r_fp32, r_int8)


def test_bf16_rerank_recall_floor():
    r_fp32 = _recall_cell("fp32", 1)
    r_bf16 = _recall_cell("bf16", 4)
    assert r_fp32 - r_bf16 <= 0.01, (r_fp32, r_bf16)
