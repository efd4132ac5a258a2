"""Kernel-level microbench: centroid navigation + posting scan hot paths.

Wall-times the XLA CPU paths and the Pallas posting-scan kernels in
interpret mode (the compiled kernels target TPU); every scan row reports
the *effective HBM bytes per query* of its schedule next to the wall time
— the traffic model the paged kernels are tiled for:

    oracle       Q·nprobe·MB pages gathered (full fixed-capacity buffers)
    per_query    only present pages, once per (query, probe)
    batched      each micro-batch-unique page once (÷ probe multiplicity)

Also times the dedup-top-k reduce rewrite against the old lexsort
reference (same candidate arrays)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lire
from repro.core.distance import MASK_DISTANCE
from repro.core.index import SPFreshIndex
from benchmarks.common import bench_cfg
from repro.data.vectors import make_sift_like


def _timeit(fn, *args, reps=5):
    jax.block_until_ready(fn(*args))  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def run(quick: bool = True) -> list[str]:
    n = 8000 if quick else 100000
    dim = 16
    nprobe = 8
    base = make_sift_like(n, dim, seed=51)
    idx = SPFreshIndex.build(bench_cfg(num_blocks=16384), base)
    state = idx.state
    cfg = state.cfg
    rng = np.random.default_rng(52)
    q_n = 256
    queries = jnp.asarray(base[rng.integers(0, n, q_n)])

    out = []

    # navigation (l2_topk target)
    nav = jax.jit(lambda s, q: lire.navigate(s, q, nprobe))
    t = _timeit(nav, state, queries)
    p = int(np.asarray(state.centroid_valid).sum())
    nav_flops = 2 * q_n * p * dim
    out.append(
        f"kernel/navigate,{t * 1e6:.1f},"
        f"flops={nav_flops};centroids={p}"
    )

    # --- scan traffic model (shared by every schedule row below) ---
    # pallas rows use a smaller query batch: interpret mode executes the
    # page grid sequentially on CPU, so Q=256 would take minutes; the
    # bytes/query model is Q-normalized either way
    from benchmarks.common import scan_traffic

    pq_n = 32
    pqueries = queries[:pq_n]
    traffic = scan_traffic(state, pqueries, nprobe)
    table = traffic["page_table"]
    present = table >= 0
    total_pages = traffic["total_pages"]
    uniq_pages = traffic["unique_pages"]
    page_bytes = traffic["page_bytes"]
    mb = cfg.max_blocks_per_posting

    def bpq(pages: float) -> float:
        return pages * page_bytes / pq_n

    # full search, oracle gather path
    srch = jax.jit(lambda s, q: lire.search(s, q, k=10, nprobe=nprobe))
    t_all = _timeit(srch, state, queries)
    out.append(
        f"kernel/search_e2e_oracle,{t_all * 1e6:.1f},"
        f"hbm_bytes_per_query={page_bytes * nprobe * mb:.0f};probe={nprobe}"
    )

    # full search, Pallas paged schedules (interpret mode on CPU — the
    # wall time is the interpreter's, the bytes/query column is the model
    # the TPU kernel realizes)
    for sched, pages in (("per_query", total_pages), ("batched", uniq_pages)):
        f = jax.jit(lambda s, q, sched=sched: lire.search(
            s, q, k=10, nprobe=nprobe,
            use_pallas_scan=True, scan_schedule=sched,
        ))
        t_s = _timeit(f, state, pqueries, reps=2)
        out.append(
            f"kernel/search_e2e_pallas_{sched},{t_s * 1e6:.1f},"
            f"hbm_bytes_per_query={bpq(pages):.0f};probe={nprobe}"
        )

    # raw per-page top-k kernel variants (scan only, no navigation/reduce)
    from repro.kernels.posting_scan import ops as scan_ops

    flat = jnp.asarray(np.where(present, table, -1))
    pvids, live = lire._page_slot_live(state, flat)
    kpage = min(10, cfg.block_size)  # per-page k, clamped like the search path
    pq = jax.jit(lambda q: scan_ops.scan_posting_blocks_topk(
        q, flat, live, state.pool.blocks, k=kpage))
    t_pq = _timeit(pq, pqueries, reps=2)
    out.append(
        f"kernel/scan_per_query_topk,{t_pq * 1e6:.1f},"
        f"hbm_bytes_per_query={bpq(total_pages):.0f};pages={total_pages}"
    )
    budget = int(2 ** np.ceil(np.log2(max(uniq_pages, 2))))
    uniqb, _, _, _ = scan_ops.dedup_pages(
        flat.reshape(-1), budget=budget, num_blocks=cfg.num_blocks
    )
    _, ulive = lire._page_slot_live(state, uniqb)
    bt = jax.jit(lambda q: scan_ops.scan_unique_blocks_topk(
        q, uniqb, ulive, state.pool.blocks, k=kpage))
    t_bt = _timeit(bt, pqueries, reps=2)
    out.append(
        f"kernel/scan_batched_topk,{t_bt * 1e6:.1f},"
        f"hbm_bytes_per_query={bpq(uniq_pages):.0f};pages={uniq_pages}"
    )

    # batch-dedup saving: unique postings probed by the batch vs total probes
    out.append(
        f"kernel/batch_dedup,0.0,"
        f"unique_pages={uniq_pages};total_pages={total_pages};"
        f"hbm_saving={total_pages / max(uniq_pages, 1):.2f}x"
    )

    # dedup-top-k reduce: lexsort reference vs top_k-prefilter rewrite
    cand = nprobe * cfg.posting_capacity
    d = jnp.asarray(rng.random((q_n, cand)), jnp.float32)
    v = jnp.asarray(rng.integers(0, n, (q_n, cand)), jnp.int32)
    m = jnp.asarray(rng.random((q_n, cand)) < 0.9)
    dm = jnp.where(m, d, MASK_DISTANCE)
    ref = jax.jit(jax.vmap(
        lambda a, b, c: lire._dedup_topk_1d_ref(a, b, c, 10)))
    new = jax.jit(jax.vmap(
        lambda a, b, c: lire._dedup_topk_1d(
            a, b, c, 10, lire._dedup_prefilter(cfg, 10, cand))))
    t_ref = _timeit(ref, dm, v, m)
    t_new = _timeit(new, dm, v, m)
    out.append(
        f"kernel/dedup_topk_lexsort_ref,{t_ref * 1e6:.1f},candidates={cand}"
    )
    out.append(
        f"kernel/dedup_topk_prefilter,{t_new * 1e6:.1f},"
        f"candidates={cand};speedup={t_ref / max(t_new, 1e-12):.2f}x"
    )

    # reassign same-vid dedup (maintenance round, _execute_reassigns):
    # O(n²) pairwise mask reference vs the sort-based first-occurrence
    # rewrite — n is the fused round's reassign budget (2·K·budget rows)
    for rows in (256, 2048):
        vids_r = jnp.asarray(
            rng.integers(0, max(rows // 4, 1), rows), jnp.int32
        )
        mask_r = jnp.asarray(rng.random(rows) < 0.7)
        refm = jax.jit(lire._dedup_vid_mask_ref)
        newm = jax.jit(lire._dedup_vid_mask)
        t_rm = _timeit(refm, vids_r, mask_r)
        t_nm = _timeit(newm, vids_r, mask_r)
        out.append(
            f"kernel/reassign_dedup_pairwise_ref,{t_rm * 1e6:.1f},rows={rows}"
        )
        out.append(
            f"kernel/reassign_dedup_sort,{t_nm * 1e6:.1f},"
            f"rows={rows};speedup={t_rm / max(t_nm, 1e-12):.2f}x"
        )
    return out


if __name__ == "__main__":
    for line in run():
        print(line)
