"""Search data-path benchmark: XLA gather oracle vs Pallas paged scan
(per-query and batch-dedup schedules).

Reports wall-clock latency percentiles AND the modeled HBM scan traffic —
the quantity the paged kernels are built to minimize:

* **oracle**      — `bp.parallel_get` gathers the full fixed-capacity
  probe buffer: ``Q · nprobe · MB`` pages regardless of occupancy.
* **per_query**   — streams only *present* pages, once per (query, probe):
  ``sum_q |pages(q)|`` page transfers.
* **batched**     — streams each micro-batch-unique page ONCE:
  ``|union_q pages(q)|`` transfers; traffic divides by the average probe
  multiplicity (how many queries probe the same page).

``run_json`` emits the machine-readable BENCH_search.json payload that
``python -m benchmarks.run --json`` writes, so the perf trajectory is
tracked across PRs.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import bench_cfg
from repro.core import lire
from repro.core.index import SPFreshIndex
from repro.data.vectors import make_sift_like

# (label, search kwargs) — the three data paths under test
PATHS = (
    ("oracle", dict()),
    ("pallas_per_query",
     dict(use_pallas_scan=True, scan_schedule="per_query")),
    ("pallas_batched",
     dict(use_pallas_scan=True, scan_schedule="batched")),
)

# (codec, rerank_factor) cells: lossy codecs over-fetch rerank_factor×k
# quantized candidates and rerank them against the exact fp32 tier
CODEC_CELLS = (("fp32", 1), ("bf16", 4), ("int8", 4))


def _build(quick: bool, codec: str = "fp32", rerank_factor: int = 1):
    n = 6000 if quick else 60000
    dim = 16
    base = make_sift_like(n, dim, seed=71)
    idx = SPFreshIndex.build(
        bench_cfg(num_blocks=16384, num_postings_cap=2048,
                  num_vectors_cap=max(65536, 2 * n),
                  codec=codec, rerank_factor=rerank_factor),
        base,
    )
    rng = np.random.default_rng(72)
    q_n = 32 if quick else 256
    # serving-shaped query mix: half uniform, half from a few hot spots
    # (trending-content skew) — probe multiplicity comes from the skew
    uni = base[rng.integers(0, n, q_n // 2)]
    hot_centers = base[rng.integers(0, n, 4)]
    hot = hot_centers[rng.integers(0, 4, q_n - q_n // 2)]
    queries = np.concatenate([uni, hot]) \
        + 0.02 * rng.normal(size=(q_n, dim)).astype(np.float32)
    return idx, jnp.asarray(queries, jnp.float32), base


def _traffic_model(state, queries, nprobe: int) -> dict:
    """Pages touched per schedule on this workload + probe multiplicity."""
    from benchmarks.common import scan_traffic

    t = scan_traffic(state, queries, nprobe)
    q_n = t["q_n"]
    return {
        "page_bytes": t["page_bytes"],
        "probe_multiplicity": t["probe_multiplicity"],
        "pages_per_query": {
            "oracle": t["oracle_pages"] / q_n,
            "pallas_per_query": t["total_pages"] / q_n,
            "pallas_batched": t["unique_pages"] / q_n,
        },
    }


def _timed(fn, reps: int) -> dict:
    jax.block_until_ready(fn())  # compile
    lats = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        lats.append((time.perf_counter() - t0) * 1e3)
    arr = np.asarray(lats)
    return {
        "mean_ms": float(arr.mean()),
        "p50_ms": float(np.percentile(arr, 50)),
        "p99_ms": float(np.percentile(arr, 99)),
    }


def _codec_cell(state, queries, gt, nprobe: int, k: int) -> dict:
    """One per-codec BENCH cell: the traffic model's page bytes (actual
    hot-tier payload itemsize + scale/zero DMA) and recall@k through the
    quantized batched Pallas path (rerank included when configured)."""
    from benchmarks.common import scan_traffic

    t = scan_traffic(state, queries, nprobe)
    _, got = lire.search(
        state, queries, k=k, nprobe=nprobe,
        use_pallas_scan=True, scan_schedule="batched",
    )
    got = np.asarray(got)
    hits = sum(
        len(set(a.tolist()) & set(b.tolist())) for a, b in zip(gt, got)
    )
    ppq = t["unique_pages"] / t["q_n"]
    return {
        "page_bytes": t["page_bytes"],
        "pages_per_query": ppq,
        "scan_bytes_per_query": ppq * t["page_bytes"],
        "recall_at_k": hits / gt.size,
    }


def run_json(quick: bool = True) -> dict:
    idx, queries, base = _build(quick)
    state = idx.state
    nprobe = 8
    k = 10
    reps = 10 if quick else 30
    model = _traffic_model(state, queries, nprobe)
    page_bytes = model["page_bytes"]

    # batched-schedule page accounting (dropped > 0 = budget dropped pages),
    # the counts the search dispatch itself returns
    *_, access = lire.search(
        state, queries, k=k, nprobe=nprobe, use_pallas_scan=True,
        scan_schedule="batched", with_access=True,
    )
    _, pages = lire.split_access(np.asarray(access))
    pstats = dict(zip(("pages_unique", "pages_dropped", "pages_grid",
                       "steps_skipped"), (int(x) for x in pages)))

    out = {
        "workload": {
            "q": int(queries.shape[0]),
            "dim": state.cfg.dim,
            "nprobe": nprobe,
            "k": k,
            "block_size": state.cfg.block_size,
            "page_bytes": page_bytes,
            "n_postings": int(np.asarray(state.n_postings)),
        },
        "probe_multiplicity": model["probe_multiplicity"],
        "page_dedup": pstats,
        "paths": {},
    }
    for label, kw in PATHS:
        fn = lambda kw=kw: lire.search(
            state, queries, k=k, nprobe=nprobe, **kw
        )
        lat = _timed(fn, reps)
        ppq = model["pages_per_query"][label]
        out["paths"][label] = {
            **lat,
            "pages_per_query": ppq,
            "scan_bytes_per_query": ppq * page_bytes,
            "scan_gb_per_query": ppq * page_bytes / 1e9,
        }
    b = out["paths"]["pallas_batched"]["scan_bytes_per_query"]
    p = out["paths"]["pallas_per_query"]["scan_bytes_per_query"]
    out["batched_traffic_saving"] = p / max(b, 1e-12)

    # per-codec cells: same workload + probe/page budgets, hot tier
    # re-encoded per codec; savings/recall compared against the fp32 cell
    from benchmarks.common import brute_force_gt

    gt = brute_force_gt(
        np.asarray(queries), base, np.arange(len(base)), k=k
    )
    cells: dict[str, dict] = {}
    for codec, rf in CODEC_CELLS:
        st = state if codec == "fp32" else _build(
            quick, codec=codec, rerank_factor=rf
        )[0].state
        cells[codec] = {
            "rerank_factor": rf,
            **_codec_cell(st, queries, gt, nprobe, k),
        }
    fp = cells["fp32"]
    for cell in cells.values():
        cell["scan_bytes_saving_vs_fp32"] = (
            fp["scan_bytes_per_query"]
            / max(cell["scan_bytes_per_query"], 1e-12)
        )
        cell["recall_delta_vs_fp32"] = (
            cell["recall_at_k"] - fp["recall_at_k"]
        )
    out["codecs"] = cells
    return out


def run(quick: bool = True) -> list[str]:
    res = run_json(quick)
    lines = []
    for label, r in res["paths"].items():
        lines.append(
            f"search_path/{label},{r['mean_ms'] * 1e3:.1f},"
            f"p50_ms={r['p50_ms']:.3f};p99_ms={r['p99_ms']:.3f};"
            f"scan_bytes_per_query={r['scan_bytes_per_query']:.0f}"
        )
    lines.append(
        "search_path/traffic,0.0,"
        f"probe_multiplicity={res['probe_multiplicity']:.2f}x;"
        f"batched_saving={res['batched_traffic_saving']:.2f}x"
    )
    for codec, c in res["codecs"].items():
        lines.append(
            f"search_path/codec_{codec},0.0,"
            f"scan_bytes_per_query={c['scan_bytes_per_query']:.0f};"
            f"saving_vs_fp32={c['scan_bytes_saving_vs_fp32']:.2f}x;"
            f"recall_delta={c['recall_delta_vs_fp32']:+.4f}"
        )
    return lines


if __name__ == "__main__":
    for line in run():
        print(line)
