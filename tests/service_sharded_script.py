"""Crash-recovery parity for the unified service API over a 2-shard mesh
— run as a subprocess with 2 fake CPU devices (spawned by
tests/test_service_api.py so the main pytest process keeps one device).

The tentpole acceptance criterion, executable: the SAME ServiceSpec
(modulo ShardSpec) opens a local and a sharded service; the sharded
service is killed before ``checkpoint`` and reopened via
``spfresh.open`` — per-shard WAL replay on top of the open-time snapshot
must answer queries with exact parity to the uncrashed run.
"""
import os
import sys
import tempfile

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=2 "
    + os.environ.get("XLA_FLAGS", "")
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

import spfresh
from repro.core.types import LireConfig
from repro.storage.wal import iter_wal

assert len(jax.devices()) == 2, jax.devices()

root = sys.argv[1] if len(sys.argv) > 1 else tempfile.mkdtemp()

CFG = LireConfig(
    dim=16, block_size=8, max_blocks_per_posting=8, num_blocks=1024,
    num_postings_cap=128, num_vectors_cap=4096, split_limit=48,
    merge_limit=6, reassign_range=8, reassign_budget=128, replica_count=2,
    nprobe=8,
)
BASE_SPEC = spfresh.ServiceSpec(
    index=spfresh.IndexSpec(config=CFG),
    serve=spfresh.ServeSpec(search_k=10, max_batch=64, min_bucket=16),
)
SPEC = BASE_SPEC.with_durability(
    os.path.join(root, "svc")).with_shards(2)


def make_clustered(rng, n, d, n_clusters=8, spread=0.05):
    centers = rng.normal(size=(n_clusters, d)).astype(np.float32)
    assign = rng.integers(0, n_clusters, size=n)
    return (centers[assign] + spread * rng.normal(size=(n, d))).astype(
        np.float32)


rng = np.random.default_rng(0)
base = make_clustered(rng, 1000, 16, n_clusters=10)

# ---- the SAME spec (modulo ShardSpec) opens both backends ----
local = spfresh.open(BASE_SPEC, vectors=base)
assert local.index is not None
svc = spfresh.open(SPEC, vectors=base)
assert svc.index is None and svc.initial_handles is not None
d_l, _ = local.search(base[:8], k=5)
d_s, _ = svc.search(base[:8], k=5)
np.testing.assert_allclose(d_l[:, 0], d_s[:, 0], rtol=1e-4)  # same corpus
local.close()
print("PASS one_spec_two_backends")

# ---- the built state is placed one shard per device, not on device 0 ----
for leaf in jax.tree_util.tree_leaves(svc.backend.stacked):
    owners = {(sh.index[0].start or 0): sh.device
              for sh in leaf.addressable_shards}
    assert sorted(owners) == [0, 1] and len(set(owners.values())) == 2, owners
print("PASS shard_per_device")

# ---- stream updates through the pipeline (no checkpoint) ----
new = make_clustered(rng, 90, 16, n_clusters=3)
handles = []
for s in range(0, 90, 30):
    h, landed = svc.insert(new[s:s + 30])
    assert landed.all()
    handles.extend(h.tolist())
handles = np.asarray(handles, np.int64)
svc.delete(handles[:10].astype(np.int32))
queries = np.concatenate([new[:12], base[:12]])
want_d, want_v = svc.search(queries, k=10)
for shard in range(2):
    wal = os.path.join(SPEC.durability.resolved_wal_dir(),
                       f"shard_{shard:03d}.wal")
    assert len(list(iter_wal(wal))) > 0, f"shard {shard} WAL empty"
print("PASS sharded_stream_walled")

# ---- crash (abandon the handle) → reopen: per-shard WAL replay ----
twin = spfresh.open(SPEC)
assert twin.recovered
got_d, got_v = twin.search(queries, k=10)
np.testing.assert_array_equal(want_v, got_v)
np.testing.assert_allclose(want_d, got_d, rtol=1e-5)
leaked = set(got_v.reshape(-1).tolist()) & set(handles[:10].tolist())
assert not leaked, f"recovery resurrected deleted handles {leaked}"
_, hit = twin.search(new[20:30], k=3)
assert (hit[:, 0] == handles[20:30]).all(), "replayed handles diverged"
assert twin.stats() == svc.stats(), "stacked stats diverged after replay"
print("PASS sharded_crash_recovery_exact_parity")

# ---- recall parity vs brute force survives recovery ----
live_vecs = np.concatenate([base, new[10:]])
live_h = np.concatenate([svc.initial_handles, handles[10:]])
bf = ((queries[:, None, :] - live_vecs[None]) ** 2).sum(-1)
gt = live_h[np.argsort(bf, axis=1)[:, :10]]


def recall(v):
    hits = sum(len(set(gt[i].tolist()) & set(v[i].tolist()))
               for i in range(len(queries)))
    return hits / (len(queries) * 10)


r_live, r_twin = recall(want_v), recall(got_v)
assert r_twin == r_live and r_twin > 0.85, (r_live, r_twin)
print(f"PASS sharded_recall_parity recall={r_twin:.3f}")

# ---- checkpoint → tail replay → drain invariants ----
twin.checkpoint()
more = make_clustered(rng, 30, 16, n_clusters=2)
h2, _ = twin.insert(more)
want2 = twin.search(more[:8], k=5)
svc3 = spfresh.open(SPEC)          # snapshot + post-checkpoint tail only
got2 = svc3.search(more[:8], k=5)
np.testing.assert_array_equal(want2[1], got2[1])
svc3.drain()
assert svc3.backlog() == 0
svc3.close()
print("PASS sharded_checkpoint_tail_replay")

# ---- delta-snapshot cycle: base → delta (per-shard files) → crash ----
from repro.storage.snapshot import SnapshotStore

store = SnapshotStore(SPEC.durability.resolved_snapshot_dir())
svc4 = spfresh.open(SPEC)          # recover from the clean close (a base)
assert store.has_base() and store.chain_len() == 0
more2 = make_clustered(rng, 24, 16, n_clusters=2)
h3, landed3 = svc4.insert(more2)
assert landed3.all()
svc4.checkpoint(delta=True)
assert store.chain_len() == 1
unit_dir = os.path.join(SPEC.durability.resolved_snapshot_dir(),
                        store._head())
shard_files = sorted(f for f in os.listdir(unit_dir) if f.endswith(".npz"))
assert shard_files == ["shard_000.npz", "shard_001.npz"], shard_files
more3 = make_clustered(rng, 12, 16, n_clusters=2)
svc4.insert(more3)                 # WAL tail on top of the delta
want3 = svc4.search(more2[:8], k=5)

svc5 = spfresh.open(SPEC)          # crash → base + delta + tail replay
assert svc5.recovered
got3 = svc5.search(more2[:8], k=5)
np.testing.assert_array_equal(want3[1], got3[1])
np.testing.assert_allclose(want3[0], got3[0], rtol=1e-5)
assert svc5.stats() == svc4.stats(), "delta-chain recovery stats diverged"
_, hit3 = svc5.search(more2[:8], k=1)
assert (hit3[:, 0] == h3[:8]).all(), "delta-chain recovery lost handles"
svc5.checkpoint(delta=False)       # compaction folds + prunes the chain
assert store.chain_len() == 0
svc5.close()
print("PASS sharded_delta_chain_cycle")

print("ALL_SERVICE_SHARDED_PASS")
