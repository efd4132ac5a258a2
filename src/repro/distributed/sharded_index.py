"""Distributed SPFresh: the index sharded over the ``model`` axis,
queries parallel over ``data`` (and ``pod``) — shard_map'd LIRE.

Design (DESIGN.md §4):
  * postings are partitioned in *centroid space* (balanced k-means over
    shards) so LIRE's reassignment locality stays shard-local;
  * each (pod, data) row holds a full replica of every index shard —
    data-axis = query parallelism / read replicas;
  * updates are replicated deterministically across rows (every replica
    applies the same jitted transition), so replicas never diverge;
  * search does a per-shard local top-k then ONE all_gather(k) over
    ``model`` — the tournament merge (O(k·M) bytes, not O(candidates));
  * vector handles are (shard, slot): global_vid = shard * N_shard + slot;
    version state is owned by exactly one shard — no cross-shard races;
  * a ``shard_alive`` mask degrades dead shards gracefully (closure
    replicas keep recall from collapsing — measured in tests).

All ops below are *global* jittable functions over a stacked state whose
leaves carry a leading (n_shards,) axis sharded P('model').
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import lire
from repro.core.clustering import balanced_kmeans
from repro.core.index import build_state
from repro.core.types import IndexState, LireConfig, make_empty_state
from repro.core.distance import MASK_DISTANCE
from repro.storage.durability import DurableBackend

Array = jax.Array


def _shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off: the
    per-shard LIRE ops mix shard-local and replicated values freely."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False,
    )


# ---------------------------------------------------------------------------
# Stacked-state helpers
# ---------------------------------------------------------------------------

def stack_states(states: list[IndexState]) -> IndexState:
    """Stack per-shard states along a new leading axis (P('model')), on
    the host: ``ShardedIndex`` then places each shard on its own device,
    so no device ever holds the whole stack."""
    return jax.tree_util.tree_map(
        lambda *xs: np.stack([np.asarray(x) for x in xs], axis=0), *states
    )


def stacked_template(cfg: LireConfig, n_shards: int) -> IndexState:
    """Abstract (shape/dtype only) stacked state — the snapshot template
    for recovery, built without touching a device."""
    abstract = jax.eval_shape(lambda: make_empty_state(cfg))
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((n_shards, *x.shape), x.dtype),
        abstract,
    )


def unstack_state(stacked: IndexState, i: int) -> IndexState:
    return jax.tree_util.tree_map(lambda x: x[i], stacked)


def _squeeze(tree):
    return jax.tree_util.tree_map(lambda x: x[0], tree)


def _expand(tree):
    return jax.tree_util.tree_map(lambda x: x[None], tree)


def _data_axes(mesh: Mesh):
    return tuple(a for a in mesh.axis_names if a != "model")


# ---------------------------------------------------------------------------
# Distributed search
# ---------------------------------------------------------------------------

def _flat_axis_index(axes):
    """Flattened linear index over one or more mesh axes (row-major)."""
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def make_search_step(
    mesh: Mesh, cfg: LireConfig, *, k: int, nprobe: int | None = None,
    shard_axes: tuple[str, ...] = ("model",), probe_chunk: int = 0,
    gprobe: int = 0, use_pallas_scan: bool | None = None,
    scan_schedule: str | None = None,
):
    """Returns a jitted ``search(state_stacked, queries, shard_alive[,
    group_index_stacked]) -> (dists (Q, k), global_vids (Q, k))``.

    queries are sharded over the data axes; the per-shard local top-k is
    merged with one all_gather over 'model' (the tournament merge).
    ``gprobe > 0`` switches navigation to the two-level group router (the
    step then takes a stacked GroupIndex as 4th argument).
    ``use_pallas_scan`` / ``scan_schedule`` select each shard's local
    posting-scan data path (None = the config flags); the batched
    schedule dedups pages *per shard* — exactly the per-micro-batch
    traffic model of the single-host path.
    """
    da = tuple(a for a in mesh.axis_names if a not in shard_axes)
    nprobe_ = nprobe or cfg.nprobe
    n_shard_vecs = cfg.num_vectors_cap

    def local(state_stacked, queries, shard_alive, *rest):
        state = _squeeze(state_stacked)
        my = _flat_axis_index(shard_axes)
        if gprobe > 0:
            from repro.core.grouping import search_grouped

            gidx = _squeeze(rest[0])
            d, v = search_grouped(
                state, gidx, queries, k=k, nprobe=nprobe_, gprobe=gprobe,
                probe_chunk=probe_chunk, use_pallas_scan=use_pallas_scan,
                scan_schedule=scan_schedule,
            )
        else:
            d, v = lire.search(
                state, queries, k=k, nprobe=nprobe_, probe_chunk=probe_chunk,
                use_pallas_scan=use_pallas_scan, scan_schedule=scan_schedule,
            )
        # globalize vids: handle = shard * N_shard + slot
        gv = jnp.where(v >= 0, my * n_shard_vecs + v, -1)
        alive = shard_alive[my]
        d = jnp.where(alive, d, MASK_DISTANCE)
        gv = jnp.where(alive, gv, -1)
        # tournament merge over the shard axes
        all_d = jax.lax.all_gather(d, shard_axes, tiled=False)   # (M, Q, k)
        all_v = jax.lax.all_gather(gv, shard_axes, tiled=False)
        all_d = all_d.reshape(-1, *d.shape)
        all_v = all_v.reshape(-1, *gv.shape)
        m, q, kk = all_d.shape
        all_d = all_d.transpose(1, 0, 2).reshape(q, m * kk)
        all_v = all_v.transpose(1, 0, 2).reshape(q, m * kk)
        neg, sel = jax.lax.top_k(-all_d, k)
        out_d = -neg
        out_v = jnp.take_along_axis(all_v, sel, axis=1)
        out_v = jnp.where(out_d < MASK_DISTANCE / 2, out_v, -1)
        return out_d, out_v

    qspec = P(da, None) if da else P(None, None)
    in_specs = [state_pspecs_for(cfg, shard_axes), qspec, P(None)]
    if gprobe > 0:
        ax = shard_axes if len(shard_axes) > 1 else shard_axes[0]
        in_specs.append(
            jax.tree_util.tree_map(lambda _: P(ax), GroupIndexSpec())
        )
    sm = _shard_map(
        local, mesh=mesh, in_specs=tuple(in_specs), out_specs=(qspec, qspec)
    )
    return jax.jit(sm)


class GroupIndexSpec:
    """Pytree stand-in with the GroupIndex structure (4 array leaves)."""

    def __new__(cls):
        from repro.core.grouping import GroupIndex

        z = jnp.zeros(())
        return GroupIndex(group_centroids=z, group_sqn=z, members=z,
                          member_valid=z)


def state_pspecs_for(
    cfg: LireConfig, shard_axes: tuple[str, ...] = ("model",)
) -> Any:
    """Leaf pspecs from an abstract empty state (avoids materializing)."""
    abstract = jax.eval_shape(lambda: make_empty_state(cfg))
    ax = shard_axes if len(shard_axes) > 1 else shard_axes[0]
    return jax.tree_util.tree_map(
        lambda x: P(ax, *([None] * x.ndim)), abstract
    )


# ---------------------------------------------------------------------------
# Distributed insert / delete
# ---------------------------------------------------------------------------

def make_insert_step(
    mesh: Mesh, cfg: LireConfig, *, shard_axes: tuple[str, ...] = ("model",)
):
    """Returns jitted ``insert(state_stacked, vecs (B, d), valid (B,)) ->
    (state, handles (B,))``.

    The update batch is REPLICATED over data rows (read-replica design);
    ownership = shard with the globally nearest centroid, computed by one
    all_gather of per-shard best distances.  Each shard allocates local
    slots for its vectors and appends; handles are psum-combined.
    ``valid`` masks out padding rows (the serving pipeline pads batches
    to fixed bucket shapes); invalid rows get handle -1.
    """
    n_shard_vecs = cfg.num_vectors_cap

    def local(state_stacked, vecs, valid):
        state = _squeeze(state_stacked)
        my = _flat_axis_index(shard_axes)
        b = vecs.shape[0]

        # my best distance per vector
        d, _ = lire.navigate(state, vecs, 1)  # (B, 1)
        all_d = jax.lax.all_gather(d[:, 0], shard_axes, tiled=False)
        all_d = all_d.reshape(-1, b)                   # (M, B)
        owner = jnp.argmin(all_d, axis=0)              # (B,)
        mine = (owner == my) & valid

        # local slot allocation for owned vectors
        order = jnp.cumsum(mine.astype(jnp.int32)) - 1
        slots = jnp.where(mine, state.next_vid + order, -1)
        cap_ok = slots < cfg.num_vectors_cap
        mine = mine & cap_ok
        n_new = jnp.sum(mine)
        state = state.replace(next_vid=state.next_vid + n_new)

        state, landed, _ = lire.insert_batch(
            state, vecs, jnp.maximum(slots, 0), mine
        )
        # a dropped primary append (posting at hard capacity) must NOT get
        # a handle — the engine's backpressure/retry path keys off -1
        ok = mine & landed

        # combine handles across shards (exactly one shard owns each vector)
        handle_part = jnp.where(ok, my * n_shard_vecs + slots, 0)
        handles = jax.lax.psum(handle_part, shard_axes)
        handles = jnp.where(
            jax.lax.psum(ok.astype(jnp.int32), shard_axes) > 0, handles, -1
        )
        return _expand(state), handles

    sm = _shard_map(
        local, mesh=mesh,
        in_specs=(state_pspecs_for(cfg, shard_axes), P(None, None), P(None)),
        out_specs=(state_pspecs_for(cfg, shard_axes), P(None)),
    )
    return jax.jit(sm, donate_argnums=(0,))


def make_delete_step(
    mesh: Mesh, cfg: LireConfig, *, shard_axes: tuple[str, ...] = ("model",)
):
    """jitted ``delete(state_stacked, handles (B,)) -> state``."""
    n_shard_vecs = cfg.num_vectors_cap

    def local(state_stacked, handles):
        state = _squeeze(state_stacked)
        my = _flat_axis_index(shard_axes)
        owner = handles // n_shard_vecs
        slot = handles % n_shard_vecs
        mine = (owner == my) & (handles >= 0)
        state = lire.delete_batch(state, slot, mine)
        return _expand(state)

    sm = _shard_map(
        local, mesh=mesh,
        in_specs=(state_pspecs_for(cfg, shard_axes), P(None)),
        out_specs=state_pspecs_for(cfg, shard_axes),
    )
    return jax.jit(sm, donate_argnums=(0,))


def make_maintenance_step(
    mesh: Mesh, cfg: LireConfig, *, shard_axes: tuple[str, ...] = ("model",),
    budget: int = 1,
):
    """jitted ``maintain(state_stacked) -> (state, n_did_work)``.

    Every shard runs ``budget`` SEQUENTIAL LIRE maintenance steps on its
    own postings (fused into one executable via lax.scan, mirroring
    ``core.index.fused_maintenance_step``).  Kept as the baseline the
    batched round is measured against; the serving path dispatches
    `make_maintenance_round`.
    """

    def local(state_stacked):
        state = _squeeze(state_stacked)

        def body(s, _):
            s, did = lire.maintenance_step(s)
            return s, did.astype(jnp.int32)

        state, dids = jax.lax.scan(body, state, None, length=budget)
        any_did = jax.lax.pmax(jnp.sum(dids), shard_axes)
        return _expand(state), any_did

    sm = _shard_map(
        local, mesh=mesh,
        in_specs=(state_pspecs_for(cfg, shard_axes),),
        out_specs=(state_pspecs_for(cfg, shard_axes), P()),
    )
    return jax.jit(sm, donate_argnums=(0,))


def make_maintenance_round(
    mesh: Mesh, cfg: LireConfig, *, shard_axes: tuple[str, ...] = ("model",),
    jobs_per_round: int = 4,
):
    """jitted ``maintain(state_stacked) -> (state, n_jobs_done)``.

    Every shard runs ONE batched `lire.maintenance_round`
    (``jobs_per_round`` splits + merges with a fused reassign pass) on its
    own postings — rebalancing is embarrassingly parallel across shards
    because the reassign neighborhood is shard-local by the centroid-space
    partition.  ``n_jobs_done`` is the max-over-shards job count, the ONE
    scalar the host drain loop reads back per round.
    """

    def local(state_stacked):
        state = _squeeze(state_stacked)
        state, did = lire.maintenance_round(state, jobs_per_round)
        any_did = jax.lax.pmax(did, shard_axes)
        return _expand(state), any_did

    sm = _shard_map(
        local, mesh=mesh,
        in_specs=(state_pspecs_for(cfg, shard_axes),),
        out_specs=(state_pspecs_for(cfg, shard_axes), P()),
    )
    return jax.jit(sm, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# Sharded build (host, offline) + elastic re-sharding
# ---------------------------------------------------------------------------

def partition_vectors(
    vectors: np.ndarray, n_shards: int, seed: int = 0
) -> tuple[np.ndarray, np.ndarray]:
    """Centroid-space partition: balanced k-means into n_shards groups.
    Returns (assignment (n,), shard_centroids (n_shards, d))."""
    if n_shards == 1:
        return (
            np.zeros(len(vectors), np.int32),
            vectors.mean(axis=0, keepdims=True).astype(np.float32),
        )
    cen, assign = balanced_kmeans(
        jax.random.PRNGKey(seed),
        jnp.asarray(vectors, jnp.float32),
        jnp.ones(len(vectors), bool),
        k=n_shards, iters=12, balance_weight=2.0,
    )
    return np.asarray(assign), np.asarray(cen)


def build_sharded_state(
    cfg: LireConfig, vectors: np.ndarray, n_shards: int, *, seed: int = 0
) -> tuple[IndexState, np.ndarray]:
    """Offline build: partition by centroid space, SPANN-build each shard,
    stack.  Returns (stacked_state, global_vid_of_input (n,)) where
    handles follow the (shard, slot) scheme."""
    assign, _ = partition_vectors(vectors, n_shards, seed)
    states, handles = [], np.full(len(vectors), -1, np.int64)
    for s in range(n_shards):
        idx = np.where(assign == s)[0]
        if len(idx) == 0:
            st = make_empty_state(cfg, seed=seed + s)
        else:
            st = build_state(cfg, vectors[idx], seed=seed + s)
            st = st.replace(next_vid=jnp.asarray(len(idx), jnp.int32))
            handles[idx] = s * cfg.num_vectors_cap + np.arange(len(idx))
        # each shard moves to the host as soon as it is built: the build
        # runs on one device, which holds a single shard at a time
        states.append(jax.device_get(st))
        del st
    return stack_states(states), handles


def gather_live_vectors(
    stacked: IndexState, n_shards: int
) -> tuple[np.ndarray, np.ndarray]:
    """Extract all live vectors (+ global handles) from a stacked state —
    the elastic re-sharding path reads a snapshot through this."""
    from repro.storage import versionmap as vm

    out_v, out_h = [], []
    # one host copy, then plain numpy slicing: indexing a shard out of a
    # mesh-sharded device array has no unambiguous output sharding
    stacked = jax.device_get(stacked)
    for s in range(n_shards):
        st = unstack_state(stacked, s)
        vids = np.asarray(st.pool.block_vid).reshape(-1)
        vers = np.asarray(st.pool.block_ver).reshape(-1)
        # re-sharding rebuilds the index from these rows, so read the
        # exact fp32 tier when the codec keeps one (no requant error)
        tier = (st.pool.blocks_exact if st.pool.blocks_exact is not None
                else st.pool.blocks)
        vecs = np.asarray(tier, dtype=np.float32).reshape(-1, st.pool.dim)
        stale = np.asarray(
            vm.is_stale(st.versions, jnp.asarray(vids), jnp.asarray(vers))
        )
        live = (vids >= 0) & ~stale
        # dedup replicas: keep first occurrence of each vid
        vids_live = vids[live]
        vecs_live = vecs[live]
        _, first = np.unique(vids_live, return_index=True)
        out_v.append(vecs_live[first])
        out_h.append(s * st.cfg.num_vectors_cap + vids_live[first])
    return np.concatenate(out_v), np.concatenate(out_h)


def reshard(
    cfg: LireConfig, stacked: IndexState, old_shards: int, new_shards: int,
    *, seed: int = 0,
) -> tuple[IndexState, np.ndarray]:
    """Elastic scaling: rebuild the partition for a different shard count
    from the live contents (snapshot-driven re-shard)."""
    vecs, _ = gather_live_vectors(stacked, old_shards)
    return build_sharded_state(cfg, vecs, new_shards, seed=seed)


# ---------------------------------------------------------------------------
# ShardedIndex — the stateful handle the serving pipeline drives
# ---------------------------------------------------------------------------

class ShardedIndex(DurableBackend):
    """Stacked sharded state + its jitted shard_map steps, behind the
    ServeEngine backend protocol (`repro.serve.engine.IndexBackend`).

    The engine feeds the same padded micro-batches it feeds a single-host
    index; every op here is one dispatch of a cached shard_map executable,
    with the stacked state donated on updates.  Search/insert/delete use
    global (shard, slot) handles; ``shard_alive`` degrades dead shards.

    Direct construction (the loose kwarg pile below) is deprecated as a
    user surface: declare a :class:`repro.api.ServiceSpec` and let
    ``spfresh.open`` build/recover the backend — that path also attaches
    the durable lifecycle (per-shard WAL + snapshot checkpoints).
    """

    def __init__(
        self,
        mesh: Mesh,
        cfg: LireConfig,
        stacked: IndexState,
        n_shards: int,
        *,
        shard_axes: tuple[str, ...] = ("model",),
        probe_chunk: int = 0,
        use_pallas_scan: bool | None = None,
        scan_schedule: str | None = None,
        jobs_per_round: int | None = None,
    ):
        self.mesh = mesh
        self.cfg = cfg
        self.n_shards = n_shards
        self.shard_axes = shard_axes
        self.adopt_state(stacked)
        self.probe_chunk = probe_chunk
        self.use_pallas_scan = use_pallas_scan
        self.scan_schedule = scan_schedule
        self.jobs_per_round = jobs_per_round or cfg.jobs_per_round
        self.shard_alive = jnp.ones((n_shards,), bool)
        self._search_steps: dict[tuple, Any] = {}
        self._maintain_steps: dict[int, Any] = {}
        self._insert_step = make_insert_step(mesh, cfg, shard_axes=shard_axes)
        self._delete_step = make_delete_step(mesh, cfg, shard_axes=shard_axes)

    @classmethod
    def build(
        cls,
        mesh: Mesh,
        cfg: LireConfig,
        vectors: np.ndarray,
        n_shards: int,
        *,
        seed: int = 0,
        shard_axes: tuple[str, ...] = ("model",),
        probe_chunk: int = 0,
        use_pallas_scan: bool | None = None,
        scan_schedule: str | None = None,
        jobs_per_round: int | None = None,
    ) -> tuple["ShardedIndex", np.ndarray]:
        """Offline sharded build; returns (index, handles of the inputs)."""
        stacked, handles = build_sharded_state(cfg, vectors, n_shards, seed=seed)
        idx = cls(mesh, cfg, stacked, n_shards, shard_axes=shard_axes,
                  probe_chunk=probe_chunk, use_pallas_scan=use_pallas_scan,
                  scan_schedule=scan_schedule, jobs_per_round=jobs_per_round)
        return idx, handles

    def set_alive(self, alive: np.ndarray) -> None:
        self.shard_alive = jnp.asarray(alive, bool)

    # ---------------- replication hooks (replica cloning) ---------------
    def fork_state(self) -> IndexState:
        """Deep copy of the stacked state.  The update steps donate their
        stacked-state argument, so a replica sharing buffers with the
        primary would be invalidated by the primary's next update."""
        return jax.tree_util.tree_map(jnp.copy, self.stacked)

    def adopt_state(self, stacked: IndexState) -> None:
        """Install a stacked state (host-built, restored, or forked),
        placed onto THIS index's mesh: shard i on the i-th device of the
        shard axes.  The replica rows of a (data, model) mesh each run
        their own single-axis submesh (see
        ``sharding.replica_submeshes``)."""
        specs = state_pspecs_for(self.cfg, self.shard_axes)
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        self.stacked = jax.device_put(stacked, shardings)

    def clone(self, mesh: Mesh | None = None) -> "ShardedIndex":
        """A read replica of this index on ``mesh`` (default: same mesh):
        same config and step geometry, its own deep-copied state, its own
        compiled steps."""
        twin = ShardedIndex(
            mesh or self.mesh, self.cfg, self.fork_state(), self.n_shards,
            shard_axes=self.shard_axes, probe_chunk=self.probe_chunk,
            use_pallas_scan=self.use_pallas_scan,
            scan_schedule=self.scan_schedule,
            jobs_per_round=self.jobs_per_round,
        )
        twin._wal_applied = self._wal_applied
        return twin

    # --------------------------- backend ops ---------------------------
    def search(
        self, queries: np.ndarray, k: int, nprobe: int | None = None,
        valid: np.ndarray | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        # ``valid`` (padded-row mask) is accepted for backend-protocol
        # parity but unused: the sharded backend does not accumulate
        # access telemetry (see ARCHITECTURE.md — the drift policy on
        # shards ranks by the update/drift leaves, which the jitted steps
        # bump deterministically; access_count stays zero).
        return self.search_begin(queries, k, nprobe, valid)()

    def search_begin(
        self, queries: np.ndarray, k: int, nprobe: int | None = None,
        valid: np.ndarray | None = None,
    ):
        """Issue ONE shard_map'd search dispatch and return a zero-arg
        ``finalize`` materializing ``(dists, ids)``; the dispatch is in
        flight when this returns, so the engine's pump thread can defer
        the host readback to scatter time (device overlap)."""
        key = (k, nprobe)
        step = self._search_steps.get(key)
        if step is None:
            step = make_search_step(
                self.mesh, self.cfg, k=k, nprobe=nprobe,
                shard_axes=self.shard_axes, probe_chunk=self.probe_chunk,
                use_pallas_scan=self.use_pallas_scan,
                scan_schedule=self.scan_schedule,
            )
            self._search_steps[key] = step
        # a copy: the queue refills its staging buffer while the dispatch
        # may still read it (on the CPU the device array aliases it)
        d, v = step(self.stacked, jnp.asarray(np.array(queries)),
                    self.shard_alive)

        def finalize():
            return np.asarray(d), np.asarray(v)
        return finalize

    def insert(
        self, vecs: np.ndarray, vids: np.ndarray, valid: np.ndarray,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Caller vids are ignored: the sharded index owns id assignment
        (global handle = shard * N_cap + slot).  Returns (handles, landed)."""
        self._log("insert", {
            "vecs": np.asarray(vecs, np.float32),
            "valid": np.asarray(valid, bool),
        })
        self.stacked, handles = self._insert_step(
            self.stacked, jnp.asarray(vecs), jnp.asarray(valid)
        )
        handles = np.asarray(handles)
        return handles, handles >= 0

    def delete(self, vids: np.ndarray, valid: np.ndarray) -> None:
        handles = np.where(np.asarray(valid), np.asarray(vids), -1)
        self._log("delete", {"handles": np.asarray(handles, np.int32)})
        self.stacked = self._delete_step(
            self.stacked, jnp.asarray(handles, jnp.int32)
        )

    def log_update(self, op: str, payload: dict) -> None:
        """Engine-level batch logging is a no-op here: the backend logs
        every update DISPATCH itself (`_log`) when a WalSet is attached —
        dispatch-level records make replay bit-deterministic (handles are
        assigned inside the jitted step, so replaying the exact dispatch
        stream reproduces them)."""

    def maintain(self, jobs: int) -> int:
        """One fused maintenance round: ``jobs`` split+merge jobs per
        shard, ONE dispatch (cached per jobs count), ONE did-work scalar
        read back.  Returns the max-over-shards jobs done."""
        self._log("maintain", {"jobs": np.asarray(jobs, np.int32)})
        step = self._maintain_steps.get(jobs)
        if step is None:
            step = make_maintenance_round(
                self.mesh, self.cfg, shard_axes=self.shard_axes,
                jobs_per_round=jobs,
            )
            self._maintain_steps[jobs] = step
        self.stacked, did = step(self.stacked)
        return int(did)

    def drain(self) -> tuple[int, int]:
        """Rounds to quiescence; returns ``(jobs_done, rounds)``."""
        total = 0
        rounds = 0
        jobs = self.jobs_per_round
        # convergence bound: at most ~2*P_cap useful jobs (§3.4)
        for _ in range(2 * self.cfg.num_postings_cap // jobs + 1):
            did = self.maintain(jobs)
            rounds += 1
            total += did
            if did == 0:
                break
        return total, rounds

    def backlog(self) -> int:
        lens = np.asarray(self.stacked.pool.posting_len)      # (M, P)
        valid = np.asarray(self.stacked.centroid_valid)       # (M, P)
        return int(((lens > self.cfg.split_limit) & valid).sum())

    # ---------------------- durability lifecycle -----------------------
    # Paper §4.4 promoted to the sharded backend (DurableBackend mixin):
    # per-shard WAL append on every update dispatch, one atomic
    # stacked-state snapshot stamping each shard's applied seqno, replay
    # through the same shard_map'd steps on open — deterministic, so
    # handles land exactly as pre-crash.  This closes the old
    # "snapshot-only" gap.

    @property
    def _wal_shards(self) -> int:
        return self.n_shards

    def _snapshot_state(self):
        return self.stacked

    def _set_snapshot_state(self, state):
        self.stacked = state

    def _snapshot_extra(self):
        return {"backend": "sharded", "n_shards": self.n_shards}

    def _lire_config(self):
        return self.cfg

    def _apply_record(self, rec) -> None:
        p = rec.payload
        if rec.op == "insert":
            self.insert(
                p["vecs"], np.full(len(p["vecs"]), -1, np.int32),
                p["valid"],
            )
        elif rec.op == "delete":
            handles = p["handles"]
            self.delete(handles, handles >= 0)
        elif rec.op == "maintain":
            self.maintain(int(p["jobs"]))
        else:
            raise ValueError(f"unknown WAL op {rec.op!r}")

    @classmethod
    def restore(
        cls,
        mesh: Mesh,
        cfg: LireConfig,
        snapshot_dir: str,
        n_shards: int,
        **kwargs: Any,
    ) -> tuple["ShardedIndex", dict]:
        """Load a stacked-state snapshot chain (base + per-shard deltas);
        returns (index, manifest).  WAL replay on top is the caller's
        move (`spfresh.open` wires ``WalSet.recover_records`` →
        ``replay``)."""
        from repro.storage.snapshot import SnapshotStore

        template = stacked_template(cfg, n_shards)
        stacked, manifest = SnapshotStore(snapshot_dir).load(template)
        extra = manifest.get("extra", {})
        if extra.get("n_shards", n_shards) != n_shards:
            raise ValueError(
                f"snapshot has {extra['n_shards']} shards, want {n_shards}"
            )
        idx = cls(mesh, cfg, stacked, n_shards, **kwargs)
        seqnos = extra.get("wal_seqnos", [-1])
        idx._wal_applied = min(seqnos) if seqnos else -1
        return idx, manifest

    def stats(self) -> dict:
        s = self.stacked.stats
        out = {
            k: int(np.asarray(getattr(s, k)).sum())
            for k in (
                "n_inserts", "n_deletes", "n_appends", "n_append_drops",
                "n_splits", "n_gc_writebacks", "n_merges",
                "n_reassign_checked", "n_reassign_candidates",
                "n_reassigned", "n_reassign_overflow",
            )
        }
        valid = np.asarray(self.stacked.centroid_valid)
        out["n_postings"] = int(valid.sum())
        out["n_shards"] = self.n_shards
        out["used_blocks"] = int(
            self.n_shards * self.stacked.pool.num_blocks_cap
            - np.asarray(self.stacked.pool.free_top).sum()
        )
        # Telemetry aggregates summed over shards (state leaves only, same
        # keys as the local backend).
        tel = self.stacked.telemetry
        out["access_total"] = int(np.asarray(tel.access_count)[valid].sum())
        out["update_total"] = int(np.asarray(tel.update_count)[valid].sum())
        out["drift_norm_total"] = float(
            np.linalg.norm(np.asarray(tel.drift_vec)[valid], axis=-1).sum()
        )
        return out
