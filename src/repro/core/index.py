"""SPFreshIndex — the user-facing index object.

Composition (paper Fig. 5):
  * offline build      — SPANN hierarchical balanced clustering + closure
                         replication (host-driven, §3.1);
  * foreground Updater — `insert`/`delete` (jitted `lire.insert_batch` /
                         `lire.delete_batch`), WAL-logged;
  * background Local Rebuilder — `maintain()` drains split/merge/reassign
                         jobs in batched rounds (jitted
                         `lire.maintenance_round`);
  * Searcher           — `search()`;
  * crash recovery     — `snapshot()` / `restore()` = snapshot + WAL replay.

The wrapper is a thin *host* convenience: all state transitions are the
functional ops in `repro.core.lire`; distributed execution wraps those same
ops in shard_map (see `repro.distributed.sharded_index`).
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lire
from repro.core.clustering import hierarchical_balanced_kmeans, pow2_bucket
from repro.core.distance import pairwise_sql2
from repro.core.types import IndexState, LireConfig, make_empty_state
from repro.storage import codec as pcodec
from repro.storage.snapshot import load_snapshot, save_snapshot, snapshot_exists
from repro.storage.wal import WriteAheadLog, iter_wal

_INSERT_CHUNK = 256
_QUERY_CHUNK = 64


@functools.partial(jax.jit, static_argnames=("r",))
def _nearest_centroids(xs, cen, cen_valid, *, r: int):
    """``(dists (n, r), pids (n, r))``: each row's r nearest valid
    centroids, nearest first."""
    d = jnp.where(cen_valid[None, :], pairwise_sql2(xs, cen), jnp.inf)
    neg_d, idx = jax.lax.top_k(-d, r)
    return -neg_d, idx


def _build_routing(
    vectors: np.ndarray,
    centroids: np.ndarray,
    assign: np.ndarray,
    cfg: LireConfig,
    chunk: int = 8192,
) -> list[list[int]]:
    """Vector → posting membership lists: primary (from the clustering) plus
    SPANN closure replicas (top-R centroids within the replica_rng ratio).

    Replicas are admitted in vid order while a posting stays within
    ``split_limit``: a fresh build hands the rebuilder no split backlog.
    The centroid table and the last chunk are padded (centroids to a
    power-of-two bucket, masked), so routing compiles one program."""
    n = vectors.shape[0]
    p = centroids.shape[0]
    members: list[list[int]] = [[] for _ in range(p)]
    for i, pid in enumerate(assign.tolist()):
        members[pid].append(i)

    if cfg.replica_count > 1 and p > 1:
        r = min(cfg.replica_count, p)
        pb = pow2_bucket(p)
        cen = np.zeros((pb, centroids.shape[1]), np.float32)
        cen[:p] = centroids
        cen_valid = np.arange(pb) < p
        factor = float(cfg.replica_rng) ** 2
        cap = cfg.split_limit
        for start in range(0, n, chunk):
            rows = vectors[start : start + chunk]
            xs = np.zeros((chunk, vectors.shape[1]), np.float32)
            xs[: len(rows)] = rows
            dists, idx = _nearest_centroids(xs, cen, cen_valid, r=r)
            dists = np.asarray(dists)[: len(rows)]
            idx = np.asarray(idx)[: len(rows)]
            own = assign[start : start + len(rows)]
            ok = (idx != own[:, None]) & (dists <= factor * dists[:, :1])
            for row, j in zip(*np.nonzero(ok)):
                pid = int(idx[row, j])
                if len(members[pid]) < cap:
                    members[pid].append(start + int(row))
    return members


def build_state(
    cfg: LireConfig,
    vectors: np.ndarray,
    *,
    seed: int = 0,
    build_posting_size: int | None = None,
) -> IndexState:
    """Offline SPANN-style build → a ready IndexState (host-constructed)."""
    cfg.validate()
    vectors = np.asarray(vectors, np.float32)
    n, d = vectors.shape
    assert d == cfg.dim, (d, cfg.dim)
    assert n <= cfg.num_vectors_cap

    target = build_posting_size or max(cfg.merge_limit + 1, int(cfg.split_limit * 0.6))
    centroids, assign = hierarchical_balanced_kmeans(
        vectors, max_posting_size=target, seed=seed
    )
    p = centroids.shape[0]
    if p > cfg.num_postings_cap:
        raise ValueError(
            f"build produced {p} postings > cap {cfg.num_postings_cap}; "
            "raise num_postings_cap or split_limit"
        )
    members = _build_routing(vectors, centroids, assign, cfg)

    bs, mb = cfg.block_size, cfg.max_blocks_per_posting
    cap = cfg.posting_capacity
    quant = cfg.codec == "int8"
    # hot tier staged at fp32 for fp32/bf16 (converted to the payload dtype
    # below); int8 encodes per posting during the fill
    blocks = np.zeros(
        (cfg.num_blocks, bs, d),
        np.int8 if quant else np.dtype(cfg.vector_dtype),
    )
    exact = (
        np.zeros((cfg.num_blocks, bs, d), np.float32)
        if pcodec.has_exact_tier(cfg.codec)
        else None
    )
    post_scale = np.ones((cfg.num_postings_cap,), np.float32)
    post_zero = np.zeros((cfg.num_postings_cap,), np.float32)
    block_vid = np.full((cfg.num_blocks, bs), -1, np.int32)
    block_ver = np.zeros((cfg.num_blocks, bs), np.uint8)
    posting_blocks = np.full((cfg.num_postings_cap, mb), -1, np.int32)
    posting_len = np.zeros((cfg.num_postings_cap,), np.int32)

    next_block = 0
    for pid in range(p):
        mem = members[pid][:cap]
        posting_len[pid] = len(mem)
        nb = math.ceil(len(mem) / bs) if mem else 0
        if next_block + nb > cfg.num_blocks:
            raise ValueError("num_blocks too small for the build")
        if mem:
            scale, zero = pcodec.np_train_scale_zero(vectors[mem])
            post_scale[pid] = scale
            post_zero[pid] = zero
        for b in range(nb):
            bid = next_block
            next_block += 1
            posting_blocks[pid, b] = bid
            rows = mem[b * bs : (b + 1) * bs]
            raw = vectors[rows]
            blocks[bid, : len(rows)] = (
                pcodec.np_encode(raw, post_scale[pid], post_zero[pid])
                if quant
                else raw
            )
            if exact is not None:
                exact[bid, : len(rows)] = raw
            block_vid[bid, : len(rows)] = rows

    state = make_empty_state(cfg, seed=seed)
    # free block stack: unused blocks
    free_blocks = np.arange(next_block, cfg.num_blocks, dtype=np.int32)
    free_stack = np.zeros((cfg.num_blocks,), np.int32)
    free_stack[: free_blocks.size] = free_blocks
    # free pid stack: unused pids
    free_pids = np.arange(p, cfg.num_postings_cap, dtype=np.int32)
    pid_stack = np.zeros((cfg.num_postings_cap,), np.int32)
    pid_stack[: free_pids.size] = free_pids

    cen = np.zeros((cfg.num_postings_cap, d), np.float32)
    cen[:p] = centroids
    cvalid = np.zeros((cfg.num_postings_cap,), bool)
    cvalid[:p] = True

    pool = state.pool.replace(
        blocks=jnp.asarray(blocks).astype(state.pool.blocks.dtype),
        blocks_exact=(
            jnp.asarray(exact) if exact is not None else None
        ),
        block_vid=jnp.asarray(block_vid),
        block_ver=jnp.asarray(block_ver),
        posting_blocks=jnp.asarray(posting_blocks),
        posting_len=jnp.asarray(posting_len),
        free_stack=jnp.asarray(free_stack),
        free_top=jnp.asarray(free_blocks.size, jnp.int32),
        post_scale=jnp.asarray(post_scale),
        post_zero=jnp.asarray(post_zero),
    )
    return state.replace(
        pool=pool,
        centroids=jnp.asarray(cen),
        centroid_sqn=jnp.asarray(np.sum(cen * cen, axis=-1)),
        centroid_valid=jnp.asarray(cvalid),
        pid_free_stack=jnp.asarray(pid_stack),
        pid_free_top=jnp.asarray(free_pids.size, jnp.int32),
    )


# ---------------------------------------------------------------------------
# Batched jit entry points (the serving pipeline's hot path)
#
# The ServeEngine feeds fixed-shape padded micro-batches straight into these
# cached executables — no host-side chunking loop, one dispatch per batch.
# Update steps donate the index state so XLA can mutate the (large) block
# pool in place instead of copying it every batch.
# ---------------------------------------------------------------------------

def _owned(x: np.ndarray) -> jax.Array:
    """A host batch as a device array over memory of its own.  On the CPU
    ``jnp.asarray`` aliases the host buffer, which an asynchronous
    dispatch reads after it returns; the copy (a few KB) keeps a refilled
    staging buffer out of a dispatch still in flight."""
    return jnp.asarray(np.array(x))


@functools.lru_cache(maxsize=None)
def search_step(
    k: int,
    nprobe: int | None,
    probe_chunk: int = 0,
    use_pallas_scan: bool | None = None,
    scan_schedule: str | None = None,
    with_access: bool = False,
):
    """jitted ``(state, queries (B, d)) -> (dists (B, k), vids (B, k))``.

    ``probe_chunk`` / ``use_pallas_scan`` / ``scan_schedule`` select the
    posting-scan data path (None defers to the state's config flags) —
    the serving pipeline threads them through from ``EngineConfig``.
    ``with_access`` adds a third output, the per-posting probe histogram
    with the dispatch's page counts appended (``lire.split_access``
    parts them): the serving backend's access telemetry and ``scan.*``
    counters.
    """
    return jax.jit(
        functools.partial(
            lire.search, k=k, nprobe=nprobe, probe_chunk=probe_chunk,
            use_pallas_scan=use_pallas_scan, scan_schedule=scan_schedule,
            with_access=with_access,
        )
    )


@functools.lru_cache(maxsize=None)
def insert_step():
    """jitted, state-donating ``(state, vecs, vids, valid) -> (state,
    landed, routes)`` (`lire.insert_batch`)."""

    def f(state, vecs, vids, valid):
        return lire.insert_batch(state, vecs, vids, valid)

    return jax.jit(f, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def delete_step():
    """jitted, state-donating ``(state, vids, valid) -> state``."""

    def f(state, vids, valid):
        return lire.delete_batch(state, vids, valid)

    return jax.jit(f, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def fused_maintenance_step(budget: int):
    """jitted, state-donating SEQUENTIAL rebuilder slot: ``budget``
    one-job-at-a-time maintenance steps in ONE executable (a lax.scan),
    returning ``(state, n_did_work)``.

    Kept as the baseline the batched round is benchmarked against
    (`benchmarks/bench_maintenance.py`); the serving pipeline dispatches
    `fused_maintenance_round` instead."""

    def f(state):
        def body(s, _):
            s, did = lire.maintenance_step(s)
            return s, did.astype(jnp.int32)

        state, dids = jax.lax.scan(body, state, None, length=budget)
        return state, jnp.sum(dids)

    return jax.jit(f, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def fused_maintenance_round(jobs: int):
    """jitted, state-donating batched rebuilder round: the top-``jobs``
    oversized postings split and bottom-``jobs`` undersized merged in ONE
    executable with a single fused reassignment pass, returning
    ``(state, n_jobs_done)``.

    Constant work regardless of how many jobs fire — the TPU idiom for the
    paper's background job queue; the host pays one dispatch and reads one
    did-work scalar per round.  The second operand is the (P_cap,) i32
    access histogram folded into the telemetry before job selection (all
    zeros when the caller has none — an exact no-op fold); the optional
    third, ``(F,)`` i32 postings to split first (``maintenance_round``'s
    ``force``)."""

    def f(state, access, force=None):
        return lire.maintenance_round(state, jobs, access, force)

    return jax.jit(f, donate_argnums=(0,))


def _pad_to(x: np.ndarray, size: int, fill=0) -> np.ndarray:
    pad = size - x.shape[0]
    if pad <= 0:
        return x
    width = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return np.pad(x, width, constant_values=fill)


class SPFreshIndex:
    """Stateful host wrapper over the functional LIRE ops."""

    def __init__(self, state: IndexState, wal_path: str | None = None):
        self.state = state
        self.wal = WriteAheadLog(wal_path) if wal_path else None
        self._wal_applied = self.wal.next_seqno - 1 if self.wal else -1
        self.last_drain_rounds = 0

    # ------------------------------------------------------------------
    @classmethod
    def build(
        cls,
        cfg: LireConfig,
        vectors: np.ndarray,
        *,
        seed: int = 0,
        wal_path: str | None = None,
    ) -> "SPFreshIndex":
        return cls(build_state(cfg, vectors, seed=seed), wal_path=wal_path)

    # ---------------------------- Updater -----------------------------
    def insert(
        self,
        vecs: np.ndarray,
        vids: np.ndarray,
        *,
        log: bool = True,
        max_retries: int = 4,
    ) -> None:
        """Foreground insert with pipeline backpressure.

        When a primary append hits a posting at hard capacity, we run the
        Local Rebuilder, whose first round splits the refused rows'
        primary postings ahead of its ranking, and retry the unlanded
        vectors — the explicit-backpressure form of the paper's
        Updater→Rebuilder feed-forward pipeline.
        """
        vecs = np.asarray(vecs, np.float32)
        vids = np.asarray(vids, np.int32)
        if log and self.wal is not None:
            self._wal_applied = self.wal.append(
                "insert", {"vecs": vecs, "vids": vids}
            )
        for s in range(0, len(vids), _INSERT_CHUNK):
            v = vecs[s : s + _INSERT_CHUNK]
            i = vids[s : s + _INSERT_CHUNK]
            for attempt in range(max_retries + 1):
                nvalid = len(i)
                if nvalid == 0:
                    break
                vp = _pad_to(v, _INSERT_CHUNK)
                ip = _pad_to(i, _INSERT_CHUNK, fill=-1)
                valid = np.arange(_INSERT_CHUNK) < nvalid
                self.state, landed, routes = lire.insert_batch(
                    self.state, jnp.asarray(vp), jnp.asarray(ip), jnp.asarray(valid)
                )
                landed = np.asarray(landed)[:nvalid]
                if landed.all() or attempt == max_retries:
                    break
                # Backpressure: the rebuilder splits the full primary
                # posting(s) first, then drains the rest.
                primary = np.asarray(routes)[:nvalid, 0]
                self.maintain(force=primary[~landed])
                v, i = v[~landed], i[~landed]

    def delete(self, vids: np.ndarray, *, log: bool = True) -> None:
        vids = np.asarray(vids, np.int32)
        if log and self.wal is not None:
            self._wal_applied = self.wal.append("delete", {"vids": vids})
        for s in range(0, len(vids), _INSERT_CHUNK):
            i = vids[s : s + _INSERT_CHUNK]
            nvalid = len(i)
            i = _pad_to(i, _INSERT_CHUNK, fill=-1)
            valid = np.arange(_INSERT_CHUNK) < nvalid
            self.state = lire.delete_batch(
                self.state, jnp.asarray(i), jnp.asarray(valid)
            )

    # ------------------------- Local Rebuilder -------------------------
    def maintain(
        self, max_steps: int | None = None, jobs_per_round: int | None = None,
        access: np.ndarray | None = None, force: np.ndarray | None = None,
    ) -> int:
        """Drain split/merge/reassign jobs in batched rounds (one did-work
        readback per round); returns jobs executed.  ``jobs_per_round``
        defaults to ``cfg.jobs_per_round``; the round count of the last
        drain is kept in ``last_drain_rounds``.  ``access`` (optional
        probe histogram) folds into the first round's selection, and
        ``force`` (optional posting ids) takes its first split slots."""
        self.state, jobs, rounds = lire.rebuild_drain(
            self.state, max_steps, jobs_per_round, donate=True, access=access,
            force=force,
        )
        self.last_drain_rounds = rounds
        return jobs

    # ---------------------------- Searcher -----------------------------
    def search(
        self, queries: np.ndarray, k: int, *, nprobe: int | None = None,
        probe_chunk: int = 0, use_pallas_scan: bool | None = None,
        scan_schedule: str | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        queries = np.asarray(queries, np.float32)
        nq = queries.shape[0]
        out_d, out_v = [], []
        for s in range(0, nq, _QUERY_CHUNK):
            q = _pad_to(queries[s : s + _QUERY_CHUNK], _QUERY_CHUNK)
            d, v = lire.search(
                self.state, jnp.asarray(q), k=k,
                nprobe=nprobe or self.state.cfg.nprobe,
                probe_chunk=probe_chunk, use_pallas_scan=use_pallas_scan,
                scan_schedule=scan_schedule,
            )
            out_d.append(np.asarray(d))
            out_v.append(np.asarray(v))
        d = np.concatenate(out_d)[:nq]
        v = np.concatenate(out_v)[:nq]
        return d, v

    # ------------------- Batched pipeline entry points -----------------
    # Fixed-shape, one-dispatch variants driven by the ServeEngine; the
    # caller (the RequestQueue) owns padding and bucket discipline, and
    # refills its staging buffers as soon as a dispatch returns, so every
    # batch argument goes in as an ``_owned`` copy.

    def search_padded(
        self, queries: np.ndarray, k: int, *, nprobe: int | None = None,
        probe_chunk: int = 0, use_pallas_scan: bool | None = None,
        scan_schedule: str | None = None, with_access: bool = False,
        qvalid: np.ndarray | None = None, as_jax: bool = False,
    ) -> tuple[np.ndarray, ...]:
        """One fixed-shape search dispatch.  ``as_jax=True`` returns the
        raw device arrays without forcing a host readback — the dispatch
        is already in flight (JAX async dispatch), so the caller can
        overlap device work with other host/device activity and convert
        with ``np.asarray`` at scatter time."""
        step = search_step(
            k, nprobe, probe_chunk, use_pallas_scan, scan_schedule,
            with_access,
        )
        if qvalid is None:
            out = step(self.state, _owned(queries))
        else:
            out = step(
                self.state, _owned(queries),
                qvalid=jnp.asarray(qvalid, bool),
            )
        if as_jax:
            return tuple(out)
        return tuple(np.asarray(x) for x in out)

    def insert_padded(
        self, vecs: np.ndarray, vids: np.ndarray, valid: np.ndarray,
    ) -> tuple[np.ndarray, np.ndarray]:
        """One donated-state insert dispatch; returns the landed mask and
        each row's primary posting (`lire.insert_batch`)."""
        self.state, landed, routes = insert_step()(
            self.state, _owned(vecs), _owned(vids), _owned(valid),
        )
        return np.asarray(landed), np.asarray(routes)[:, 0]

    def delete_padded(self, vids: np.ndarray, valid: np.ndarray) -> None:
        self.state = delete_step()(
            self.state, _owned(vids), _owned(valid)
        )

    def maintain_round(
        self, jobs: int | None = None, access: np.ndarray | None = None,
        force: np.ndarray | None = None,
    ) -> int:
        """One fused rebuilder round (``jobs`` split+merge jobs + one
        fused reassign pass, one dispatch); returns how many jobs acted.
        ``access`` is the serving backend's pending probe histogram
        (None folds zeros — an exact no-op); ``force`` the postings whose
        split takes the round's first slots."""
        jobs = jobs or self.state.cfg.jobs_per_round
        if access is None:
            access = np.zeros(
                (self.state.cfg.num_postings_cap,), np.int32
            )
        if force is not None:
            force = jnp.asarray(force, jnp.int32)
        self.state, did = fused_maintenance_round(jobs)(
            self.state, jnp.asarray(access, jnp.int32), force
        )
        return int(did)

    # Pre-round name for the one-dispatch maintenance slot; the budget is
    # now a jobs-per-round count.
    maintain_fused = maintain_round

    def maintain_fused_seq(self, budget: int) -> int:
        """One SEQUENTIAL fused slot (``budget`` one-job steps, one
        dispatch) — the benchmark baseline for the batched round."""
        self.state, did = fused_maintenance_step(budget)(self.state)
        return int(did)

    def backlog(self) -> int:
        """Rebuild backlog: postings currently over the split limit."""
        lens = np.asarray(self.state.pool.posting_len)
        valid = np.asarray(self.state.centroid_valid)
        return int(((lens > self.state.cfg.split_limit) & valid).sum())

    # ------------------------- Crash recovery --------------------------
    def snapshot(self, path: str) -> None:
        save_snapshot(
            path, self.state, extra={"wal_seqno": self._wal_applied}
        )
        if self.wal is not None:
            self.wal.truncate()

    @classmethod
    def restore(
        cls,
        path: str,
        cfg: LireConfig,
        *,
        wal_path: str | None = None,
    ) -> "SPFreshIndex":
        """Latest snapshot + WAL replay (paper §4.4)."""
        template = make_empty_state(cfg)
        if snapshot_exists(path):
            state, manifest = load_snapshot(path, template)
            after = manifest["extra"].get("wal_seqno", -1)
        else:
            state, after = template, -1
        idx = cls.__new__(cls)
        idx.state = state
        idx.wal = None
        idx._wal_applied = after
        idx.last_drain_rounds = 0
        if wal_path and os.path.exists(wal_path):
            for rec in iter_wal(wal_path, after_seqno=after):
                if rec.op == "insert":
                    idx.insert(rec.payload["vecs"], rec.payload["vids"], log=False)
                elif rec.op == "delete":
                    idx.delete(rec.payload["vids"], log=False)
                idx._wal_applied = rec.seqno
        if wal_path:
            idx.wal = WriteAheadLog(wal_path)
        return idx

    # ---------------------------- Accounting ---------------------------
    def stats(self) -> dict:
        s = self.state.stats
        out = {
            k: int(getattr(s, k))
            for k in (
                "n_inserts", "n_deletes", "n_appends", "n_append_drops",
                "n_splits", "n_gc_writebacks", "n_merges",
                "n_reassign_checked", "n_reassign_candidates",
                "n_reassigned", "n_reassign_overflow",
            )
        }
        out["n_postings"] = int(self.state.n_postings)
        out["used_blocks"] = int(
            self.state.pool.num_blocks_cap - self.state.pool.free_top
        )
        # Telemetry aggregates read the STATE leaves only — never the
        # serving backend's host-side pending-access buffer — so two
        # services whose WALs replayed identically report identical stats.
        tel = self.state.telemetry
        valid = np.asarray(self.state.centroid_valid)
        out["access_total"] = int(np.asarray(tel.access_count)[valid].sum())
        out["update_total"] = int(np.asarray(tel.update_count)[valid].sum())
        out["drift_norm_total"] = float(
            np.linalg.norm(
                np.asarray(tel.drift_vec)[valid], axis=-1
            ).sum()
        )
        return out

    def memory_bytes(self) -> dict:
        """Resource accounting analogous to paper Fig. 7(d): what must sit in
        'DRAM' (centroids + mappings + versions) vs 'disk' (block payloads).

        ``hot`` is the scan-path payload (codec dtype + per-posting quant
        params); ``cold`` the exact tier a lossy codec carries; ``disk``
        their sum plus slot metadata."""
        st = self.state
        in_mem = (
            st.centroids.size * 4
            + st.centroid_sqn.size * 4
            + st.centroid_valid.size
            + st.versions.size
            + st.pool.posting_blocks.size * 4
            + st.pool.posting_len.size * 4
            + st.pool.free_stack.size * 4
            + st.pid_free_stack.size * 4
        )
        hot = (
            st.pool.blocks.size * st.pool.blocks.dtype.itemsize
            + st.pool.post_scale.size * 4
            + st.pool.post_zero.size * 4
        )
        cold = (
            st.pool.blocks_exact.size * st.pool.blocks_exact.dtype.itemsize
            if st.pool.blocks_exact is not None
            else 0
        )
        on_disk = (
            hot
            + cold
            + st.pool.block_vid.size * 4
            + st.pool.block_ver.size
        )
        return {"memory": in_mem, "disk": on_disk, "hot": hot, "cold": cold}
