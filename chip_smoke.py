#!/usr/bin/env python3
"""Bring-up smoke for the SPFresh service on TPU.

Drives the main path once through ``spfresh.open`` at the per-shard
geometry of the spfresh-1b configuration (``configs/spfresh.py``
``CONFIG_PAGED``: d=100 int8 pages of 32 vectors, 262,144 pages, 65,536
postings, 4M handles, nprobe=64, the batch-dedup Pallas scan):

1. build 1,000,000 live SPACEV-shaped byte vectors (seeded; 100,000 per
   chip with ``--chips 4``, which costs four chips per second);
2. search 1,024 queries at k=10 and check recall@10 against a float32
   brute-force reference computed on the host;
3. one update epoch at the paper's 1% rate (deletes + inserts in batches
   of 4,096), drain the rebuilder, search again: recall holds and no
   deleted id comes back;
4. checkpoint, close, reopen from the durable root: the recovered service
   answers exactly as before the close;
5. the search step's lowered program calls the compiled Pallas kernels
   (``tpu_custom_call``), not the interpreter.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # document-sharded: one shard per chip

Exits non-zero, printing no result, when JAX finds no TPU or any phase
fails.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Times printed here are one-off bring-up readings, not benchmark results.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SMOKE_ROOT = os.path.join(REPO, ".smoke_root")

RECALL_FLOOR = 0.90
# live vectors at build by chip count: 1M on one chip; on four, 100k per
# shard (each shard keeps the full per-shard capacity) so the sharded run
# stays a few minutes of a four-chip host
LIVE_DEFAULT = {1: 1_000_000, 4: 400_000}


class PhaseError(RuntimeError):
    """A smoke phase produced a wrong result."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseError(what)


def _log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# The plain reference: chunked float32 brute force on the host
# ---------------------------------------------------------------------------

def brute_force_topk(vecs, ids, queries, k: int, chunk: int = 65536):
    """Exact k nearest live vectors by squared L2, ``(dists (Q, k) f64,
    ids (Q, k))`` nearest first.  Scans ``vecs (N, d)`` in row chunks with
    a running top-k, so nothing of size (Q, N, d) — or (Q, N) — exists."""
    import numpy as np

    q = np.asarray(queries, np.float32)
    qsq = np.sum(q.astype(np.float64) ** 2, axis=1)[:, None]
    best_d = np.full((len(q), 0), np.inf)
    best_i = np.zeros((len(q), 0), np.int64)
    for s in range(0, len(vecs), chunk):
        x = np.asarray(vecs[s:s + chunk], np.float32)
        xsq = np.sum(x.astype(np.float64) ** 2, axis=1)[None, :]
        d = qsq + xsq - 2.0 * (q @ x.T).astype(np.float64)
        cat_d = np.concatenate([best_d, d], axis=1)
        cat_i = np.concatenate(
            [best_i, np.broadcast_to(ids[s:s + chunk], d.shape)], axis=1
        )
        sel = np.argpartition(cat_d, min(k, cat_d.shape[1] - 1), axis=1)[:, :k]
        best_d = np.take_along_axis(cat_d, sel, axis=1)
        best_i = np.take_along_axis(cat_i, sel, axis=1)
    order = np.argsort(best_d, axis=1, kind="stable")
    return (np.take_along_axis(best_d, order, axis=1),
            np.take_along_axis(best_i, order, axis=1))


def recall_at_k(found, queries, ref_d, live) -> float:
    """Tie-aware recall@k: the share of the k answers per query that are
    distinct live ids no farther than the reference's k-th distance.
    ``live`` maps id -> vector row (a dict)."""
    import numpy as np

    k = ref_d.shape[1]
    hits = 0
    for qi, row in enumerate(found):
        seen = set()
        for vid in row.tolist():
            if vid < 0 or vid in seen or vid not in live:
                continue
            seen.add(vid)
            diff = live[vid].astype(np.float64) - queries[qi]
            if float(diff @ diff) <= ref_d[qi, -1] + 1e-6:
                hits += 1
    return hits / (len(found) * k)


# ---------------------------------------------------------------------------
# The phases (platform-independent: tests run them on the CPU)
# ---------------------------------------------------------------------------

def make_data(n: int, n_update: int, n_queries: int, dim: int, seed: int):
    """``(base, pool, queries)`` SPACEV-shaped byte vectors from one
    seeded draw: the index contents, the insert pool, held-out queries."""
    from repro.data.vectors import make_spacev_bytes

    data = make_spacev_bytes(n + n_update + n_queries, dim, seed)
    return data[:n], data[n:n + n_update], data[n + n_update:]


def run_phases(spec, *, n: int, n_queries: int = 1024, k: int = 10,
               update_rate: float = 0.01, batch: int = 4096, seed: int = 0,
               log=_log) -> dict:
    """Build → search → update epoch + drain → search → checkpoint, close,
    recover → search.  Raises :class:`PhaseError` on a wrong result and
    returns the readings plus the recovered (open) service under
    ``"service"`` — the caller closes it."""
    import numpy as np

    import spfresh
    from repro.utils.compile_cache import CompileCounter

    cfg = spec.lire_config()
    n_update = max(1, int(round(update_rate * n)))
    base, pool, queries = make_data(n, n_update, n_queries, cfg.dim, seed)
    out: dict = {"n": n, "dim": cfg.dim, "queries": n_queries}

    t = time.perf_counter()
    with CompileCounter() as cc:
        svc = spfresh.open(spec, vectors=base, fresh=True)
    out["build_s"] = time.perf_counter() - t
    out["build_compiles"] = cc.compiles
    out["build_compile_s"] = cc.seconds
    handles = np.asarray(svc.initial_handles, np.int64)
    live = dict(zip(handles.tolist(), base))
    n_live = len(live)
    log(f"build: live={n_live} d={cfg.dim} seconds={out['build_s']:.1f} "
        f"compiles={cc.compiles} compile_s={cc.seconds:.1f}")
    _check(n_live == n and len(set(handles.tolist())) == n,
           f"build returned {len(set(handles.tolist()))} handles for {n} rows")

    def search(tag):
        with CompileCounter() as cc:
            t = time.perf_counter()
            d, v = svc.search(queries, k=k)
            first = time.perf_counter() - t
        t = time.perf_counter()
        d, v = svc.search(queries, k=k)
        warm = time.perf_counter() - t
        _check(d.shape == (n_queries, k) and v.shape == (n_queries, k),
               f"{tag}: result shape {d.shape}")
        _check(bool(np.isfinite(d[v >= 0]).all()), f"{tag}: non-finite dists")
        ids = np.fromiter(live.keys(), np.int64, len(live))
        ref_d, _ = brute_force_topk(
            np.stack(list(live.values())), ids, queries, k
        )
        rec = recall_at_k(v, queries, ref_d, live)
        log(f"{tag}: recall@{k}={rec:.4f} first_s={first:.3f} "
            f"warm_s={warm:.3f} compiles={cc.compiles} "
            f"compile_s={cc.seconds:.1f}")
        out[f"{tag}_recall"] = rec
        out[f"{tag}_first_s"] = first
        out[f"{tag}_warm_s"] = warm
        out[f"{tag}_compile_s"] = cc.seconds
        _check(rec >= RECALL_FLOOR,
               f"{tag}: recall@{k} {rec:.4f} < {RECALL_FLOOR}")
        return d, v

    search("search_initial")

    # -- one update epoch at the paper's rate, then drain the rebuilder --
    rng = np.random.default_rng(seed + 1)
    doomed = rng.choice(handles, size=n_update, replace=False)
    t = time.perf_counter()
    # An insert the service did not acknowledge (its posting was full
    # after the engine's backpressure rounds) is retried by the client
    # after a drain, as the Updater contract asks; nothing unacknowledged
    # counts as written.
    new_ids = np.full(n_update, -1, np.int64)
    with CompileCounter() as cc:
        for s in range(0, n_update, batch):
            svc.delete(doomed[s:s + batch])
            rows = np.arange(s, min(s + batch, n_update))
            vids = None if spec.sharded else n + rows
            ids, landed = svc.insert(pool[rows], vids)
            new_ids[rows] = np.where(landed, ids, -1)
        jobs = svc.drain()
        retried = int((new_ids < 0).sum())
        for _ in range(3):
            rows = np.nonzero(new_ids < 0)[0]
            if not len(rows):
                break
            ids, landed = svc.insert(pool[rows],
                                     None if spec.sharded else n + rows)
            new_ids[rows] = np.where(landed, ids, -1)
            jobs += svc.drain()
        backlog = svc.backlog()
    out["update_s"] = time.perf_counter() - t
    out["insert_retried"] = retried
    _check(bool((new_ids >= 0).all()),
           f"update: {int((new_ids < 0).sum())} inserts never acknowledged")
    for h in doomed.tolist():
        del live[h]
    _check(len(set(new_ids.tolist()) & set(live)) == 0,
           "update: an insert reused a live id")
    live.update(zip(new_ids.tolist(), pool))
    log(f"update: deleted={n_update} inserted={len(new_ids)} "
        f"retried_after_drain={retried} "
        f"drain_jobs={jobs} backlog={backlog} live={len(live)} "
        f"seconds={out['update_s']:.1f} compiles={cc.compiles} "
        f"compile_s={cc.seconds:.1f}")
    _check(backlog == 0, f"update: backlog {backlog} after drain")
    _check(len(live) == n, f"update: live count {len(live)} != {n}")

    d_before, v_before = search("search_updated")
    returned_deleted = int(np.isin(v_before, doomed).sum())
    out["deleted_returned"] = returned_deleted
    log(f"update: deleted ids returned={returned_deleted}")
    _check(returned_deleted == 0, "update: deleted ids were returned")

    # -- crash recovery: checkpoint, close, reopen from the durable root --
    t = time.perf_counter()
    svc.checkpoint()
    svc.close()
    with CompileCounter() as cc:
        svc = spfresh.open(spec)
        d_after, v_after = svc.search(queries, k=k)
    out["recover_s"] = time.perf_counter() - t
    same = bool(np.array_equal(v_after, v_before)
                and np.array_equal(d_after, d_before))
    out["recovered_identical"] = same
    log(f"recovery: recovered={svc.recovered} identical={same} "
        f"seconds={out['recover_s']:.1f} compiles={cc.compiles}")
    _check(svc.recovered, "recovery: the reopened service did not recover")
    _check(same, "recovery: answers differ from those before the close")
    out["service"] = svc
    return out


def search_step_text(svc, n_queries: int) -> str:
    """Lowered text of the service's search step at one micro-batch."""
    import jax
    import jax.numpy as jnp

    spec = svc.spec
    eng = spec.engine_config()
    q = jnp.zeros((min(n_queries, eng.max_batch), spec.lire_config().dim),
                  jnp.float32)
    b = svc.backend
    if spec.sharded:
        from repro.distributed.sharded_index import make_search_step

        step = make_search_step(
            b.mesh, b.cfg, k=eng.search_k, nprobe=eng.nprobe,
            shard_axes=b.shard_axes, probe_chunk=b.probe_chunk,
            use_pallas_scan=b.use_pallas_scan, scan_schedule=b.scan_schedule,
        )
        return step.lower(b.stacked, q, b.shard_alive).as_text()
    from repro.core.index import search_step

    step = search_step(eng.search_k, eng.nprobe, b.probe_chunk,
                       b.use_pallas_scan, b.scan_schedule, True)
    return step.lower(
        b.index.state, q, qvalid=jnp.ones((q.shape[0],), bool)
    ).as_text()


def shard_devices(svc) -> list[set]:
    """For every leaf of the stacked state, the set of devices holding
    each shard index — one device per shard when placed correctly."""
    import jax

    per_leaf = []
    for leaf in jax.tree_util.tree_leaves(svc.backend.stacked):
        owners: dict[int, set] = {}
        for sh in leaf.addressable_shards:
            start = sh.index[0].start or 0
            owners.setdefault(start, set()).add(sh.device)
        per_leaf.append(owners)
    return per_leaf


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the local service on one chip; 4: the "
                         "document-sharded service, one shard per chip")
    ap.add_argument("--n", type=int, default=None,
                    help="live vectors at build, all shards together "
                         "(default: 1,000,000 on one chip, 400,000 on four)")
    ap.add_argument("--queries", type=int, default=1024)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(REPO, "src"))
    try:
        import jax
    except ImportError as e:
        print(f"chip_smoke: JAX is not importable: {e}", file=sys.stderr)
        return 2
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r}); the "
              "smoke does not fall back to the CPU", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    try:
        from repro.configs.spfresh import service_spec
        from repro.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's sources are missing: {e}",
              file=sys.stderr)
        return 2

    cache = enable_compile_cache()
    n = args.n or LIVE_DEFAULT[args.chips]
    _log(f"device: platform={dev.platform} kind={dev.device_kind} "
         f"count={len(devices)} chips_used={args.chips} cache={cache}")
    shutil.rmtree(SMOKE_ROOT, ignore_errors=True)
    spec = service_spec(paged=True, n_shards=args.chips,
                        durable_root=SMOKE_ROOT)
    try:
        res = run_phases(spec, n=n, n_queries=args.queries, seed=args.seed)
        svc = res.pop("service")
        text = search_step_text(svc, args.queries)
        kernels = text.count("tpu_custom_call")
        _log(f"kernels: tpu_custom_call in search step = {kernels}")
        _check(kernels > 0, "the search step runs no compiled Pallas kernel")
        if args.chips > 1:
            owners = shard_devices(svc)
            devs = {d for leaf in owners for s in leaf.values() for d in s}
            ok = all(
                len(leaf) == args.chips
                and all(len(s) == 1 for s in leaf.values())
                and len({next(iter(s)) for s in leaf.values()}) == args.chips
                for leaf in owners
            )
            _log(f"shards: {len(owners)} leaves, one shard per device="
                 f"{ok}, devices={sorted(str(d) for d in devs)}")
            _check(ok, "the stacked state is not one shard per device")
        svc.close()
    except PhaseError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(SMOKE_ROOT, ignore_errors=True)
    _log("summary: " + json.dumps(
        {k: (round(v, 4) if isinstance(v, float) else v)
         for k, v in res.items()}))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
