"""One run of one cell: set-up, the open-loop window, the checks.

Set-up makes the data from the seed, builds the index through
``spfresh.open`` (async serving, durable root, WAL on), snapshots the
build, and warms up every bucket shape the window uses (search, insert
and delete at each micro-batch bucket, plus one maintenance round).

The window drives ``Service.engine.submit_search / submit_insert /
submit_delete`` from a seeded open-loop schedule over several submitter
threads; every request is one row and is timed from its scheduled
arrival to its ticket's ``t_done`` (an update's ``t_done`` is its
acknowledgement after the covering WAL fsync).  A stall watch
(``bench/hoststall.py``) times every stop of the process's Python
threads inside the window.

After the window every request is awaited (up to a minute past the
close), held-out queries measure recall, the vectors of acknowledged
inserts and deletes are searched for, the device's peak memory is read,
the service is closed, and then the answers are compared with the plain
reference (``bench/reference.py``) over the live set the host tracked:
acknowledged inserts in, acknowledged deletes out.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import pathlib
import shutil
import sys
import threading
import time

import numpy as np

from bench import datagen, hoststall, reference, registry, trace_reduce
from bench.traffic import DELETE, INSERT, SEARCH, Schedule, make_schedule

ROOT = registry.ROOT
OUT = ROOT / "bench" / ".out"
CACHE_DIR = OUT / "jax_cache"
N_RECALL = 1024          # held-out queries scored after the window
N_SELF = 256             # acknowledged inserts / deletes searched for
ROOF_BATCHES = 8         # 128-query dispatches traced for the roofline
REF_DEPTH = 64           # reference depth before live-set filtering
COMPLETE_GRACE_S = 60.0  # how long past the close a request may take
STREAM_VICTIM, STREAM_SAMPLE = 21, 22


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_chips(chips: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX platform is {devs[0].platform!r}, not tpu")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips; JAX sees "
                            f"{len(devs)}")
    return devs


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment says, so that two checkouts share
    nothing; every program is cached."""
    import jax

    path = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def service_spec(config: dict, root: str):
    """The configuration as ``spfresh.open`` takes it.  The build's own
    seed stays the spec's default on every run: the base set is the same
    on every seed, and so is the index built from it."""
    import spfresh
    from repro.core.types import LireConfig

    return spfresh.ServiceSpec(
        index=spfresh.IndexSpec(config=LireConfig(**config["index"])),
        serve=spfresh.ServeSpec(**config["serve"]),
        scan=spfresh.ScanSpec(**config["scan"]),
        maintenance=spfresh.MaintenanceSpec(**config["maintenance"]),
        durability=spfresh.DurabilitySpec(root=root, **config["durability"]),
    )


def buckets(config: dict) -> list[int]:
    """The micro-batch bucket ladder the engine pads to."""
    lo, hi = int(config["serve"]["min_bucket"]), int(config["serve"]["max_batch"])
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    return out + [hi]


# ---------------------------------------------------------------------------
# What the host tracks: requests, acknowledgements, the live set
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Window:
    """The requests of one window and what became of them."""

    start: float
    seconds: float
    op: np.ndarray                 # (n,) SEARCH / INSERT / DELETE
    t_sched: np.ndarray            # (n,) absolute perf_counter
    payload: np.ndarray            # query row / insert id / delete id
    tickets: list
    t_submit: np.ndarray
    compiles: int = 0
    counters0: dict | None = None
    counters1: dict | None = None
    trace_events: list | None = None
    trace_bounds: tuple | None = None
    answered: np.ndarray | None = None   # (n,) completed (updates: acked)
    refused: np.ndarray | None = None    # (n,) an insert answered "refused"
    host: dict | None = None             # hoststall.StallWatch.summary

    @property
    def close(self) -> float:
        return self.start + self.seconds


class Session:
    """One configuration under one traffic mix at one seed, on one
    service: set-up, any number of windows, then the checks."""

    def __init__(self, config: dict, traffic: dict, seed: int, *,
                 n_search: int, n_insert: int, n_delete: int,
                 workdir: pathlib.Path):
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.k = int(config["serve"]["search_k"])
        self.buckets = buckets(config)
        warm = sum(self.buckets)
        n_query = n_search + N_RECALL + ROOF_BATCHES * self.buckets[-1] + warm
        t = time.perf_counter()
        data = datagen.make_cell_data(
            config["data"], seed, n_insert=n_insert + warm, n_query=n_query,
            inserts=traffic.get("inserts", "model"),
            queries=traffic.get("queries", "model"))
        self.base, self.ins, self.qry = data["base"], data["insert"], data["query"]
        self.n_base = len(self.base)
        self.victims = datagen.rng_for(seed, STREAM_VICTIM).permutation(
            self.n_base)[:n_delete + warm]
        self.data_s = time.perf_counter() - t
        self.cur = {"query": 0, "insert": 0, "victim": 0}
        # acknowledgement times (perf_counter) and insert submit times
        self.ins_ack: dict[int, float] = {}
        self.ins_sub: dict[int, float] = {}
        self.del_ack: dict[int, float] = {}
        self.workdir = workdir
        self.svc = None

    # ------------------------------ data ------------------------------
    def take(self, kind: str, n: int) -> np.ndarray:
        """The next ``n`` query rows, insert ids or delete ids."""
        s = self.cur[kind]
        self.cur[kind] = s + n
        if kind == "query":
            return np.arange(s, s + n)
        if kind == "insert":
            return self.n_base + np.arange(s, s + n)
        return self.victims[s:s + n]

    def vec(self, ids: np.ndarray) -> np.ndarray:
        ids = np.asarray(ids, np.int64)
        out = np.empty((len(ids), self.base.shape[1]), np.int8)
        b = ids < self.n_base
        out[b] = self.base[ids[b]]
        out[~b] = self.ins[ids[~b] - self.n_base]
        return out

    def known(self, ids: np.ndarray) -> np.ndarray:
        ids = np.asarray(ids, np.int64)
        return (ids >= 0) & (ids < self.n_base + self.cur["insert"])

    # ----------------------------- set-up -----------------------------
    def open(self) -> dict:
        import spfresh

        shutil.rmtree(self.workdir, ignore_errors=True)
        spec = service_spec(self.config, str(self.workdir))
        t = time.perf_counter()
        self.svc = spfresh.open(spec, vectors=self.base.astype(np.float32),
                                fresh=True)
        build_s = time.perf_counter() - t
        t = time.perf_counter()
        # the build's durability point: a full snapshot before serving
        self.svc.checkpoint(delta=False)
        snap_s = time.perf_counter() - t
        return {"build_s": build_s, "snapshot_s": snap_s}

    def warm_up(self) -> float:
        """Compile (or load from the cache) every bucket shape of search,
        insert and delete, and one maintenance round."""
        eng = self.svc.engine
        t = time.perf_counter()
        for b in self.buckets:
            rows = self.take("query", b)
            eng.submit_search(self.qry[rows].astype(np.float32)).result()
            ids = self.take("insert", b)
            tk = eng.submit_insert(self.vec(ids).astype(np.float32),
                                   ids.astype(np.int32))
            self._note_insert(ids, tk)
            tk = eng.submit_delete(ids.astype(np.int32))
            tk.result()
            for i in ids.tolist():
                self.del_ack[i] = tk.t_done
        self.svc.maintain()
        self.svc.flush()
        return time.perf_counter() - t

    def _note_insert(self, ids, ticket) -> None:
        _, landed = ticket.result()
        for i, ok in zip(ids.tolist(), np.asarray(landed).tolist()):
            self.ins_sub[i] = ticket.t_submit
            if ok:
                self.ins_ack[i] = ticket.t_done

    # ----------------------------- counters ---------------------------
    def counters(self) -> dict:
        """The program's own counters, read under the engine's lock."""
        with self.svc.engine.exclusive():
            rep = self.svc.engine.report()
            wal = self.svc.backend.wal_set.stats()
            backlog = self.svc.backlog()
        return {"batches": rep["queue"]["batches"],
                "rows": rep["queue"]["rows"],
                "maint_time_s": rep["maintenance"]["time_s"],
                "maint_slots": rep["maintenance"]["slots"],
                "fsyncs": wal["fsyncs"], "backlog": backlog,
                "insert_dropped": rep["insert_dropped"]}

    # ----------------------------- window -----------------------------
    def run_window(self, sched: Schedule, seconds: float, *,
                   trace_dir: str | None = None) -> Window:
        from repro.utils.compile_cache import CompileCounter
        import jax

        n = len(sched.t)
        payload = np.zeros(n, np.int64)
        for kind, name in ((SEARCH, "query"), (INSERT, "insert"),
                           (DELETE, "victim")):
            sel = np.nonzero(sched.op == kind)[0]
            payload[sel] = self.take(name, len(sel))
        eng = self.svc.engine
        start = time.perf_counter() + 0.25
        win = Window(start=start, seconds=seconds, op=sched.op,
                     t_sched=start + sched.t, payload=payload,
                     tickets=[None] * n, t_submit=np.zeros(n))
        errors: list[BaseException] = []

        def submitter(tid: int) -> None:
            try:
                for j in range(tid, n, sched.threads):
                    wait = win.t_sched[j] - time.perf_counter()
                    if wait > 0:
                        time.sleep(wait)
                    op, p = sched.op[j], payload[j]
                    if op == SEARCH:
                        tk = eng.submit_search(
                            self.qry[p:p + 1].astype(np.float32))
                    elif op == INSERT:
                        tk = eng.submit_insert(
                            self.vec([p]).astype(np.float32),
                            np.asarray([p], np.int32))
                    else:
                        tk = eng.submit_delete(np.asarray([p], np.int32))
                    win.tickets[j] = tk
                    win.t_submit[j] = tk.t_submit
            except BaseException as e:  # noqa: BLE001 — reported below
                errors.append(e)

        win.counters0 = self.counters()
        threads = [threading.Thread(target=submitter, args=(i,), daemon=True,
                                    name=f"submitter-{i}")
                   for i in range(sched.threads)]
        watch = hoststall.StallWatch().start()
        try:
            with CompileCounter() as cc:
                for th in threads:
                    th.start()
                if trace_dir is not None:
                    slice_s = min(4.0, seconds / 4)
                    lo = start + seconds / 2 - slice_s / 2
                    _sleep_until(lo)
                    shutil.rmtree(trace_dir, ignore_errors=True)
                    jax.profiler.start_trace(trace_dir,
                                             profiler_options=_trace_options())
                    with jax.profiler.TraceAnnotation(trace_reduce.SLICE_SPAN):
                        _sleep_until(lo + slice_s)
                    jax.profiler.stop_trace()
                _sleep_until(win.close)
                win.counters1 = self.counters()
                win.compiles = cc.compiles
        finally:
            watch.stop()
        win.host = watch.summary(start, win.close)
        for th in threads:
            th.join(COMPLETE_GRACE_S)
        if errors:
            raise errors[0]
        self._await(win)
        if trace_dir is not None:
            win.trace_events = trace_reduce.load_xplane(
                trace_reduce.find_xplane(trace_dir))
            win.trace_bounds = trace_reduce.slice_bounds(win.trace_events)
        return win

    def _await(self, win: Window) -> None:
        """Wait for every request, a minute past the close at most; note
        the acknowledgements of the updates."""
        deadline = win.close + COMPLETE_GRACE_S
        win.answered = np.zeros(len(win.tickets), bool)
        win.refused = np.zeros(len(win.tickets), bool)
        for j, tk in enumerate(win.tickets):
            if tk is None:
                continue
            try:
                tk.result(timeout=max(0.0, deadline - time.perf_counter()))
            except TimeoutError:
                continue
            win.answered[j] = True
            if win.op[j] == INSERT:
                self._note_insert(win.payload[j:j + 1], tk)
                win.refused[j] = tk.dropped > 0
            elif win.op[j] == DELETE:
                self.del_ack[int(win.payload[j])] = tk.t_done

    # --------------------------- after the window ---------------------
    def search(self, rows_or_vecs: np.ndarray) -> tuple[np.ndarray, np.ndarray, float]:
        """Search int8 vectors through the service; ``(dists, ids, t_submit)``."""
        tk = self.svc.engine.submit_search(rows_or_vecs.astype(np.float32))
        d, v = tk.result()
        return np.asarray(d), np.asarray(v, np.int64), tk.t_submit

    def post_window(self) -> dict:
        """Held-out recall queries and the self-queries of acknowledged
        inserts and deletes, on the service as the window left it."""
        self.svc.engine.barrier()
        rng = datagen.rng_for(self.seed, STREAM_SAMPLE)
        rows = self.take("query", N_RECALL)
        d, v, ts = self.search(self.qry[rows])
        ins = np.asarray(sorted(i for i in self.ins_ack
                                if i not in self.del_ack), np.int64)
        ins = rng.choice(ins, size=min(N_SELF, len(ins)), replace=False) \
            if len(ins) else ins
        dels = np.asarray(sorted(self.del_ack), np.int64)
        dels = rng.choice(dels, size=min(N_SELF, len(dels)), replace=False) \
            if len(dels) else dels
        out = {"recall_rows": rows, "recall_d": d, "recall_v": v,
               "recall_t": ts}
        for name, ids in (("ins", ins), ("del", dels)):
            if len(ids):
                dd, vv, _ = self.search(self.vec(ids))
            else:
                dd = np.zeros((0, self.k))
                vv = np.zeros((0, self.k), np.int64)
            out[name + "_ids"], out[name + "_d"], out[name + "_v"] = ids, dd, vv
        return out

    def index_leaves(self) -> dict:
        """The one adapter onto the index's state that the roofline reads:
        centroids, their validity and the posting lengths."""
        with self.svc.engine.exclusive():
            st = self.svc.index.state
            return {"centroids": np.asarray(st.centroids, np.float32),
                    "valid": np.asarray(st.centroid_valid, bool),
                    "posting_len": np.asarray(st.pool.posting_len, np.int64),
                    "block_size": int(st.cfg.block_size),
                    "dim": int(st.cfg.dim),
                    "itemsize": int(np.dtype(st.cfg.vector_dtype).itemsize),
                    "nprobe": int(self.config["serve"]["nprobe"])}

    def roofline_batches(self, trace_dir: str) -> dict:
        """``ROOF_BATCHES`` full batches of held-out queries, one dispatch
        each, under the profiler; the work each needed, from the index's
        state as the numpy of ``scan_need`` counts it."""
        import jax

        self.svc.engine.barrier()
        leaves = self.index_leaves()
        q = self.buckets[-1]
        batches = [self.qry[self.take("query", q)] for _ in range(ROOF_BATCHES)]
        need = [scan_need(leaves, b) for b in batches]
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())
        with jax.profiler.TraceAnnotation(trace_reduce.SLICE_SPAN):
            for b in batches:
                self.search(b)
        jax.profiler.stop_trace()
        events = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
        return {"events": events,
                "bytes": float(sum(n["bytes"] for n in need)),
                "flops": float(sum(n["flops"] for n in need)),
                "batches": ROOF_BATCHES}

    def close(self) -> None:
        if self.svc is not None:
            self.svc.close()
            self.svc = None
            gc.collect()
        shutil.rmtree(self.workdir, ignore_errors=True)


def scan_need(leaves: dict, queries: np.ndarray) -> dict:
    """The bytes and operations one batched posting scan needs: the
    distinct pages held by each query's ``nprobe`` nearest live postings
    (each streamed once per batch), and a multiply-add per coordinate for
    every (query, vector) pair those postings hold."""
    c, valid = leaves["centroids"], leaves["valid"]
    q = queries.astype(np.float32)
    d = ((q.astype(np.float64) ** 2).sum(1)[:, None]
         + (c.astype(np.float64) ** 2).sum(1)[None, :]
         - 2.0 * (q @ c.T).astype(np.float64))
    d[:, ~valid] = np.inf
    nprobe = min(leaves["nprobe"], int(valid.sum()))
    probes = np.argpartition(d, nprobe - 1, axis=1)[:, :nprobe]
    plen = leaves["posting_len"]
    bs, dim = leaves["block_size"], leaves["dim"]
    pages = -(-plen // bs)
    uniq = np.unique(probes)
    page_bytes = bs * dim * leaves["itemsize"]
    return {"bytes": float(pages[uniq].sum() * page_bytes + q.nbytes),
            "flops": float(2.0 * dim * plen[probes].sum())}


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


# ---------------------------------------------------------------------------
# The checks: the program's answers against the plain reference
# ---------------------------------------------------------------------------

def live_at(s: Session, ids: np.ndarray, t: float) -> np.ndarray:
    """Whether each id was live for a search submitted at ``t``: a base
    vector or an acknowledged insert, with no acknowledged delete."""
    out = np.zeros(len(ids), bool)
    for j, i in enumerate(ids.tolist()):
        if i < 0:
            continue
        born = i < s.n_base or s.ins_ack.get(i, math.inf) < t
        out[j] = born and not s.del_ack.get(i, math.inf) < t
    return out


def reference_answers(s: Session, queries: np.ndarray, times: list[float],
                      *, precision_bits: int = 8,
                      live_override=None) -> tuple[np.ndarray, np.ndarray]:
    """The reference's top-k for each query over the set live at its
    submit time (``live_override(ids, t)`` replaces that set)."""
    pool_ids = np.concatenate([
        np.arange(s.n_base, dtype=np.int64),
        np.asarray(sorted(s.ins_ack), np.int64)])
    pool = s.vec(pool_ids)
    live_fn = live_override or (lambda ids, t: live_at(s, ids, t))
    cd, ci = reference.brute_force_topk(
        pool, pool_ids, queries, REF_DEPTH, precision_bits=precision_bits)
    k = s.k
    out_d = np.full((len(queries), k), np.inf)
    out_i = np.full((len(queries), k), -1, np.int64)
    for r, t in enumerate(times):
        ok = live_fn(ci[r], t)
        if ok.sum() < k:            # too many dead near this query: exact
            mask = live_fn(pool_ids, t)
            dd, ii = reference.brute_force_topk(
                pool[mask], pool_ids[mask], queries[r:r + 1], k,
                precision_bits=precision_bits)
            out_d[r], out_i[r] = dd[0], ii[0]
        else:
            out_d[r], out_i[r] = cd[r][ok][:k], ci[r][ok][:k]
    return out_d, out_i


def may_return(s: Session, i: int, t: float) -> bool:
    """Whether a search submitted at ``t`` may return id ``i``: a base
    vector or an insert submitted before it, not deleted (acknowledged)
    before it."""
    born = i < s.n_base or s.ins_sub.get(i, math.inf) < t
    return born and not s.del_ack.get(i, math.inf) < t


def score(s: Session, queries, times, found_d, found_v, ref_d) -> dict:
    """Tie-aware recall gap and the worst distance error of answers
    ``(found_d, found_v)`` against reference distances ``ref_d``."""
    k = s.k
    hits, err, unknown = 0, 0.0, 0
    for r in range(len(queries)):
        ids = found_v[r]
        known = s.known(ids)
        unknown += int(((ids >= 0) & ~known).sum())
        exact = np.full(k, np.inf)
        if known.any():
            exact[known] = reference.sq_dist(
                s.vec(ids[known]),
                np.broadcast_to(queries[r], (int(known.sum()), queries.shape[1])))
        hits += reference.recall_row(
            ids, exact, float(ref_d[r, k - 1]),
            lambda i, t=times[r]: may_return(s, i, t))
        got = found_d[r][known]
        if got.size:
            rel = np.abs(got - exact[known]) / np.maximum(exact[known], 1.0)
            err = max(err, float(rel.max()))
    if unknown:
        err = math.inf
    return {"recall_gap": 1.0 - hits / (len(queries) * k),
            "dist_err": err, "unknown_ids": unknown}


def deleted_returned(s: Session, found_v: np.ndarray, times) -> int:
    """Answers holding an id whose delete was acknowledged before the
    search was submitted."""
    n = 0
    for r, t in enumerate(times):
        for i in found_v[r].tolist():
            if i >= 0 and s.del_ack.get(i, math.inf) < t:
                n += 1
    return n


def insert_missing(s: Session, ids, found_d, found_v, *,
                   attempted: int) -> float | None:
    """Share of acknowledged inserts that a search for their own vector
    does not return (unless k answers tie at distance 0).  Where the
    window sent inserts and none was acknowledged, every one is missing."""
    if not len(ids):
        return 1.0 if attempted else None
    miss = 0
    for r, i in enumerate(ids.tolist()):
        row = found_v[r]
        if i in row.tolist():
            continue
        known = s.known(row)
        zero = 0
        if known.any():
            v = s.vec(row[known])
            zero = int((reference.sq_dist(
                v, np.broadcast_to(s.vec([i])[0], v.shape)) == 0).sum())
        if zero < s.k:
            miss += 1
    return miss / len(ids)


def insert_refused(win: Window) -> float | None:
    """Share of the window's answered inserts that the program refused
    (answered "not written"): speed bought by dropping writes shows here."""
    ins = (win.op == INSERT) & win.answered
    if not ins.any():
        return None
    return float((win.refused & ins).sum() / ins.sum())


def window_searches(s: Session, win: Window, sample: int):
    """All completed searches of the window, and a seeded sample of them
    for the recall comparison."""
    rows = list(np.nonzero((win.op == SEARCH) & win.answered)[0])
    rng = datagen.rng_for(s.seed, STREAM_SAMPLE + 1)
    pick = np.sort(rng.choice(len(rows), size=min(sample, len(rows)),
                              replace=False)) if rows else np.zeros(0, int)
    return rows, [rows[i] for i in pick]


def check(s: Session, win: Window, post: dict, *, answers=None) -> dict:
    """The compared numbers of one run.  ``answers`` replaces the
    program's answers (the control): a function ``(queries, times) ->
    (dists, ids)``."""
    all_rows, sample = window_searches(s, win, int(s.traffic["check_sample"]))
    q_s = s.qry[win.payload[sample]]
    t_s = [float(win.t_submit[j]) for j in sample]
    q_p = s.qry[post["recall_rows"]]
    t_p = [post["recall_t"]] * len(q_p)
    ref_d, _ = reference_answers(s, np.concatenate([q_s, q_p]), t_s + t_p)
    ref_s, ref_p = ref_d[:len(q_s)], ref_d[len(q_s):]
    if answers is None:
        res = [win.tickets[j].result() for j in sample]
        fd = np.concatenate([r[0] for r in res]) if res else np.zeros((0, s.k))
        fv = np.concatenate([r[1] for r in res]).astype(np.int64) \
            if res else np.zeros((0, s.k), np.int64)
        pd, pv = post["recall_d"], post["recall_v"]
        all_res = [win.tickets[j].result() for j in all_rows]
        all_v = np.concatenate([r[1] for r in all_res]).astype(np.int64) \
            if all_res else np.zeros((0, s.k), np.int64)
        all_t = [float(win.t_submit[j]) for j in all_rows]
        ins_d, ins_v = post["ins_d"], post["ins_v"]
        del_v = post["del_v"]
    else:
        fd, fv = answers(q_s, t_s)
        pd, pv = answers(q_p, t_p)
        all_v, all_t = fv, t_s
        ins_q = s.vec(post["ins_ids"])
        ins_d, ins_v = answers(ins_q, [math.inf] * len(ins_q))
        _, del_v = answers(s.vec(post["del_ids"]), [math.inf] * len(post["del_ids"]))
    w = score(s, q_s, t_s, fd, fv, ref_s)
    p = score(s, q_p, t_p, pd, pv, ref_p)
    checks = {
        "recall_gap": w["recall_gap"],
        "post_recall_gap": p["recall_gap"],
        "dist_err": max(w["dist_err"], p["dist_err"]),
        "deleted_returned": float(
            deleted_returned(s, all_v, all_t)
            + deleted_returned(s, pv, t_p)
            + deleted_returned(s, del_v, [math.inf] * len(del_v))),
        "insert_missing": insert_missing(
            s, post["ins_ids"], ins_d, ins_v,
            attempted=int((win.op == INSERT).sum())),
        "insert_refused": (insert_refused(win) if answers is None
                           else answers.refused),
    }
    if not len(post["del_ids"]) and not s.del_ack:
        checks["deleted_returned"] = None
    return {"checks": checks, "recall_at_10": 1.0 - p["recall_gap"],
            "n_sample": len(sample), "n_searches": len(all_rows)}


def control_answers(s: Session, *, precision_bits: int = 8,
                    stale: bool = False, refuse: bool = False):
    """The reference put in the program's place: ``precision_bits=4`` is
    the int4 control; ``stale=True`` answers over the build's set as if
    no update had been applied (a step that returns its state unchanged);
    ``refuse=True`` refuses every insert and applies every delete."""
    def live_stale(ids, t):
        return (ids >= 0) & (ids < s.n_base)

    def live_refused(ids, t):
        return live_stale(ids, t) & np.asarray(
            [not s.del_ack.get(i, math.inf) < t for i in ids.tolist()], bool)

    def answers(queries, times):
        return reference_answers(
            s, queries, times, precision_bits=precision_bits,
            live_override=(live_refused if refuse
                           else live_stale if stale else None))
    answers.refused = 1.0 if refuse else 0.0
    return answers


# ---------------------------------------------------------------------------
# Readings → metrics
# ---------------------------------------------------------------------------

def readings(s: Session, win: Window, *, setup_s: float, recall: float,
             roof: dict | None, device_kind: str) -> dict:
    """Everything a metric reader (``bench/metrics/<name>.py``) may read."""
    lat = {"search": [], "update": []}
    done_in_window = 0
    acked_in_window = 0
    for j, tk in enumerate(win.tickets):
        # a refused insert wrote nothing: it has no acknowledgement to time
        if not win.answered[j] or win.refused[j]:
            continue
        key = "search" if win.op[j] == SEARCH else "update"
        lat[key].append((tk.t_done - win.t_sched[j]) * 1e3)
        if tk.t_done <= win.close:
            if key == "search":
                done_in_window += 1
            else:
                acked_in_window += 1
    c0, c1 = win.counters0, win.counters1
    return {
        "setup_s": setup_s,
        "window_s": win.seconds,
        "lat_ms": {k: np.asarray(v) for k, v in lat.items()},
        "searches_done_in_window": done_in_window,
        "updates_acked_in_window": acked_in_window,
        "recall_at_10": recall,
        "delta": {k: c1[k] - c0[k] for k in c0},
        "backlog_end": c1["backlog"],
        "host": win.host,
        "trace_events": win.trace_events,
        "trace_bounds": win.trace_bounds,
        "roof": roof,
        "device_kind": device_kind,
    }


def lateness_ms(win: Window) -> tuple[float, float]:
    sub = win.t_submit > 0
    late = (win.t_submit[sub] - win.t_sched[sub]) * 1e3
    return (float(np.median(late)), float(late.max())) if late.size else (0.0, 0.0)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, bench: dict | None = None,
             config: dict | None = None, traffic: dict | None = None,
             require_tpu: bool = True, control: bool = False,
             workdir: pathlib.Path | None = None) -> dict:
    """One run of ``workload``; returns the result line's object.
    ``config`` / ``traffic`` replace the files the cell names, and
    ``workdir`` the durable root (tests run a cell at a size the CPU
    holds)."""
    bench = bench or registry.load()
    cell = registry.workload(bench, workload)
    config = config or registry.config(bench, cell["config"])
    traffic = traffic or registry.traffic(cell["traffic"])
    devs = require_chips(int(cell["chips"])) if require_tpu else None
    import jax

    if devs is None:
        devs, cache = jax.devices(), "off"
    else:
        cache = enable_cache()
    sched = make_schedule(traffic, seed, seconds)
    s = Session(config, traffic, seed, n_search=sched.count(SEARCH),
                n_insert=sched.count(INSERT), n_delete=sched.count(DELETE),
                workdir=workdir or OUT / "root" / workload)
    try:
        opened = s.open()
        warm_s = s.warm_up()
        setup_s = time.perf_counter() - t_start
        log("machine: " + " ".join(f"{k}={v}" for k, v in
                                   hoststall.machine().items()))
        log(f"setup: setup_s={setup_s:.3f} data_s={s.data_s:.3f} "
            f"build_s={opened['build_s']:.3f} "
            f"snapshot_s={opened['snapshot_s']:.3f} warm_s={warm_s:.3f} "
            f"cache={cache}")
        trace_dir = str(OUT / "trace" / workload) if trace else None
        win = s.run_window(sched, seconds, trace_dir=trace_dir)
        late_med, late_max = lateness_ms(win)
        log(f"window: requests={len(sched.t)} rate={traffic['rate_per_s']} "
            f"compiles_in_window={win.compiles} "
            f"lateness_median_ms={late_med:.3f} lateness_max_ms={late_max:.3f}")
        log("host: " + " ".join(f"{k}={v:.3f}" if isinstance(v, float)
                                else f"{k}={v}" for k, v in win.host.items()))
        post = s.post_window()
        roof = s.roofline_batches(str(OUT / "trace" / (workload + ".roof"))) \
            if trace else None
        if roof:
            from bench.metrics._common import SCAN_KERNEL
            from bench.peaks import peaks
            pk = peaks(devs[0].device_kind)
            log(f"roofline: bytes={roof['bytes']:.0f} flops={roof['flops']:.0f} "
                f"bytes_bound_s={roof['bytes'] / pk['hbm_bytes_per_s']:.6f} "
                f"flops_bound_s={roof['flops'] / pk['flops_bf16']:.6f} "
                f"kernel_s={trace_reduce.kernel_s(roof['events'], SCAN_KERNEL)[0]:.6f}")
        stats = devs[0].memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
        s.close()
        t = time.perf_counter()
        log(f"after: post_and_roof_s={t - win.close:.3f}")
        result = check(s, win, post)
        if control:
            result["control_int4"] = check(
                s, win, post, answers=control_answers(s, precision_bits=4)
            )["checks"]
            result["control_stale"] = check(
                s, win, post, answers=control_answers(s, stale=True)
            )["checks"]
            result["control_refuse"] = check(
                s, win, post, answers=control_answers(s, refuse=True)
            )["checks"]
        log(f"checks: reference_s={time.perf_counter() - t:.3f} "
            f"sampled={result['n_sample']} searches={result['n_searches']} "
            f"insert_refused={int(win.refused.sum())}")
    finally:
        s.close()
    r = readings(s, win, setup_s=setup_s, recall=result["recall_at_10"],
                 roof=roof, device_kind=devs[0].device_kind)
    lat = r["lat_ms"]
    log("latency: " + " ".join(
        f"{k}_p{q}_ms={np.percentile(v, q):.3f}"
        for k, v in lat.items() if v.size for q in (50, 90, 95, 99))
        + f" searches={lat['search'].size} updates={lat['update'].size}"
        f" refused_inserts={int(win.refused.sum())}")
    metrics = {}
    for m in registry.cell_metrics(bench, workload, trace):
        v = registry.metric_reader(m["name"])(r)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    attempted = len(sched.t)
    lost = attempted - int(win.answered.sum())
    # a refused insert (its posting full after the engine's backpressure
    # rounds) was answered "not written": it fails, but is no wrong answer
    failed = lost + int(win.refused.sum())
    limits = config["limits"]
    checks = {}
    for name, value in result["checks"].items():
        if value is None or name not in limits:
            continue
        checks[name] = {"value": value, "limit": float(limits[name])}
    checks["unanswered"] = {"value": float(lost), "limit": 0.0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    out["lateness_ms"] = {"median": late_med, "max": late_max}
    out["host"] = win.host
    if trace and win.trace_bounds is not None:
        lo, hi = win.trace_bounds
        device["busy_s"] = trace_reduce.busy_s(win.trace_events, lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        out["breakdown"] = trace_reduce.breakdown(win.trace_events, lo, hi)
    for name in ("control_int4", "control_stale", "control_refuse"):
        if name in result:
            out[name] = result[name]
    for name, c in checks.items():
        log(f"check: {name}={c['value']!r} limit={c['limit']!r}")
    out["checks"] = checks
    return out
