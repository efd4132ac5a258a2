"""Batched LIRE maintenance: round drain vs sequential step drain.

The Local Rebuilder must keep pace with a 1%-daily update firehose using
a sliver of compute (paper §5.2, Fig. 7/9).  The sequential driver pays
a full-centroid GEMM, a ``reassign_range`` neighbor gather, a ``route``
pass, and a device→host bool sync PER JOB; ``lire.maintenance_round``
amortizes all four over the round's K jobs (one wide GEMM, one batched
block scatter, one fused reassign pass, one did-work readback).

Rows report drain wall-clock to quiescence on a hot-region churn
workload, splits/sec and reassigns/sec, host syncs paid, and the
engine-level insert stall (serve-path time burned in backpressure slots).

``python -m benchmarks.run --json BENCH_update.json`` writes the
machine-readable report tracked across PRs.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import bench_cfg
from repro.core import lire
from repro.core.index import SPFreshIndex, build_state
from repro.serve.engine import EngineConfig, LocalBackend, ServeEngine
from repro.serve.policy import RatioPolicy


def _churned_state(n: int, seed: int = 33, jobs_per_round: int = 4):
    """Build + hot-region inserts + clustered deletes, NO maintenance:
    the rebuild backlog the drains race on."""
    cfg = bench_cfg(num_blocks=16384, jobs_per_round=jobs_per_round)
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(12, 16)) * 5
    base = (
        centers[rng.integers(0, 12, n)] + rng.normal(size=(n, 16))
    ).astype(np.float32)
    state = build_state(cfg, base)

    # Hot inserts around several centers (oversize postings, no splits yet).
    hot = n // 5
    picks = rng.integers(0, 4, hot)
    ins = (
        centers[picks] + 0.05 * rng.normal(size=(hot, 16))
    ).astype(np.float32)
    idx = SPFreshIndex(state)
    idx.insert(ins, np.arange(n, n + hot, dtype=np.int32), max_retries=0)
    # Clustered deletes (undersize postings for the merge path).
    d = ((base - centers[8]) ** 2).sum(-1)
    victims = np.argsort(d)[: n // 6].astype(np.int32)
    idx.delete(victims)
    return idx.state, {"n": n, "hot_inserts": hot, "deletes": len(victims)}


def _copy_state(state):
    """Deep-copy the device buffers: several drain variants run donating
    executables, which would delete the shared start state."""
    return jax.tree_util.tree_map(jnp.copy, state)


def _stats_of(state) -> dict:
    return {
        k: int(getattr(state.stats, k))
        for k in ("n_splits", "n_merges", "n_gc_writebacks", "n_reassigned")
    }


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


def _seq_step_drain(state):
    """The pre-round driver: one bool device→host sync per split+merge step."""
    jobs = 0
    syncs = 0
    for _ in range(2 * state.cfg.num_postings_cap):
        state, did = lire.maintenance_step(state)
        syncs += 1
        jobs += 1
        if not bool(did):
            break
    return state, jobs, syncs


def _seq_fused_drain(state, budget: int = 8):
    """PR-1 production path: lax.scan of `budget` sequential steps per
    dispatch, one count readback per slot."""
    from repro.core.index import fused_maintenance_step

    step = fused_maintenance_step(budget)
    jobs = 0
    syncs = 0
    for _ in range(2 * state.cfg.num_postings_cap // budget + 1):
        state, did = step(state)
        syncs += 1
        d = int(did)
        jobs += d
        if d == 0:
            break
    return state, jobs, syncs


def _round_drain(state, jobs_per_round: int):
    # donate: the bench hands each drain its own state copy
    state, jobs, rounds = lire.rebuild_drain(
        state, jobs_per_round=jobs_per_round, donate=True
    )
    return state, jobs, rounds


def _timed_drain(drain, state0, **kw):
    """Warm the jit cache with one full drain, then time a second from a
    fresh copy of the start state (copies happen outside the timer)."""
    out = drain(_copy_state(state0), **kw)
    jax.block_until_ready(out[0].pool.posting_len)
    before = _stats_of(state0)
    start = _copy_state(state0)
    jax.block_until_ready(start.pool.posting_len)
    t0 = time.perf_counter()
    state, jobs, syncs = drain(start, **kw)
    jax.block_until_ready(state.pool.posting_len)
    dt = time.perf_counter() - t0
    d = _delta(before, _stats_of(state))
    return {
        "wall_s": dt,
        "jobs": jobs,
        "syncs": syncs,
        "splits": d["n_splits"],
        "merges": d["n_merges"],
        "gc_writebacks": d["n_gc_writebacks"],
        "reassigned": d["n_reassigned"],
        "splits_per_s": d["n_splits"] / dt if dt > 0 else 0.0,
        "reassigns_per_s": d["n_reassigned"] / dt if dt > 0 else 0.0,
    }


class _SeqBackend(LocalBackend):
    """LocalBackend whose maintenance slots run the SEQUENTIAL fused step
    (the PR-1 path) instead of the batched round — the insert-stall
    baseline."""

    def maintain(self, jobs, force=None):
        # the baseline's slot picks its jobs by size alone: ``force``,
        # the backpressure's full postings, is not steered
        return self.index.maintain_fused_seq(jobs)


def _insert_stall(state0, *, seq: bool, jobs: int, seed: int = 77) -> dict:
    """Hot-region insert stream under churn: total insert wall time and
    the slice of it burned in backpressure maintenance slots."""
    rng = np.random.default_rng(seed)
    idx = SPFreshIndex(_copy_state(state0))
    backend = _SeqBackend(idx) if seq else LocalBackend(idx)
    engine = ServeEngine(
        backend,
        EngineConfig(search_k=10, maintain_budget=jobs, max_batch=128),
        policy=RatioPolicy(ratio=2, budget=jobs),
    )
    hot = np.asarray(state0.centroids)[np.asarray(state0.centroid_valid)][0]
    n_ins = 384
    vecs = (hot[None, :] + 0.05 * rng.normal(size=(n_ins, 16))).astype(
        np.float32
    )
    vids = np.arange(50_000, 50_000 + n_ins, dtype=np.int32)
    # warm the compile caches (insert step AND the maintenance executable)
    # outside the timed window
    engine.insert(vecs[:8], vids[:8])
    backend.maintain(jobs)
    t0 = time.perf_counter()
    for s in range(8, n_ins, 128):
        engine.insert(vecs[s : s + 128], vids[s : s + 128])
    wall = time.perf_counter() - t0
    rep = engine.report()
    return {
        "insert_wall_s": wall,
        "stall_s": rep["insert_stall_s"],
        "retries": rep["insert_retries"],
        "maint_slots": rep["maintenance"]["slots"],
        "maint_jobs": rep["maintenance"]["steps"],
    }


def run_json(quick: bool = True) -> dict:
    n = 6000 if quick else 40000
    state0, wl = _churned_state(n)
    lens = np.asarray(state0.pool.posting_len)
    valid = np.asarray(state0.centroid_valid)
    wl["backlog_oversized"] = int(
        ((lens > state0.cfg.split_limit) & valid).sum()
    )
    wl["backlog_undersized"] = int(
        ((lens < state0.cfg.merge_limit) & valid).sum()
    )

    seq = _timed_drain(lambda s: _seq_step_drain(s), state0)
    seq_fused = _timed_drain(lambda s: _seq_fused_drain(s, budget=8), state0)
    rounds = {}
    for j in (4, 8):
        r = _timed_drain(lambda s, j=j: _round_drain(s, j), state0)
        r["rounds"] = r.pop("syncs")
        rounds[str(j)] = r

    stall_seq = _insert_stall(state0, seq=True, jobs=8)
    stall_round = _insert_stall(state0, seq=False, jobs=8)

    return {
        "bench": "maintenance",
        "quick": quick,
        "workload": wl,
        "sequential_step_drain": seq,
        "sequential_fused_drain_b8": seq_fused,
        "round_drain": rounds,
        "round_speedup_vs_step": {
            j: seq["wall_s"] / max(r["wall_s"], 1e-9)
            for j, r in rounds.items()
        },
        "round_speedup_vs_fused": {
            j: seq_fused["wall_s"] / max(r["wall_s"], 1e-9)
            for j, r in rounds.items()
        },
        "insert_stall": {
            "sequential_b8": stall_seq,
            "round_j8": stall_round,
            "stall_reduction": stall_seq["stall_s"]
            / max(stall_round["stall_s"], 1e-9),
        },
    }


def run(quick: bool = True) -> list[str]:
    rep = run_json(quick=quick)
    out = []

    def drain_row(name, r, extra=""):
        out.append(
            f"maintenance/{name},{r['wall_s'] * 1e6:.1f},"
            f"jobs={r['jobs']};splits={r['splits']};merges={r['merges']};"
            f"reassigned={r['reassigned']};"
            f"splits_per_s={r['splits_per_s']:.1f};"
            f"reassigns_per_s={r['reassigns_per_s']:.1f}{extra}"
        )

    seq = rep["sequential_step_drain"]
    drain_row("seq_step_drain", seq, f";syncs={seq['syncs']}")
    sf = rep["sequential_fused_drain_b8"]
    drain_row("seq_fused_drain_b8", sf, f";syncs={sf['syncs']}")
    for j, r in rep["round_drain"].items():
        sp = rep["round_speedup_vs_step"][j]
        drain_row(
            f"round_drain_j{j}", r,
            f";rounds={r['rounds']};speedup_vs_step={sp:.2f}x",
        )
    for name, s in (
        ("insert_stall_seq_b8", rep["insert_stall"]["sequential_b8"]),
        ("insert_stall_round_j8", rep["insert_stall"]["round_j8"]),
    ):
        out.append(
            f"maintenance/{name},{s['insert_wall_s'] * 1e6:.1f},"
            f"stall_s={s['stall_s']:.3f};retries={s['retries']};"
            f"maint_slots={s['maint_slots']};maint_jobs={s['maint_jobs']}"
        )
    return out


if __name__ == "__main__":
    for line in run():
        print(line)
