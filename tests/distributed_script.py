"""Multi-device sharded-index checks — run as a subprocess with 8 fake CPU
devices (spawned by tests/test_distributed.py so the main pytest process
keeps exactly one device)."""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.index import SPFreshIndex, build_state
from repro.core.types import LireConfig
from repro.distributed import sharded_index as D

assert len(jax.devices()) == 8, jax.devices()

MESH = jax.make_mesh((2, 4), ("data", "model"))
CFG = LireConfig(
    dim=16, block_size=8, max_blocks_per_posting=8, num_blocks=1024,
    num_postings_cap=128, num_vectors_cap=4096, split_limit=48,
    merge_limit=6, reassign_range=8, reassign_budget=128, replica_count=2,
    nprobe=8,
)


def make_clustered(rng, n, d, n_clusters=8, spread=0.05):
    centers = rng.normal(size=(n_clusters, d)).astype(np.float32)
    assign = rng.integers(0, n_clusters, size=n)
    return (centers[assign] + spread * rng.normal(size=(n, d))).astype(np.float32)


rng = np.random.default_rng(0)
base = make_clustered(rng, 2000, 16, n_clusters=12)

# ---- build sharded over 4 model shards ----
stacked, handles = D.build_sharded_state(CFG, base, 4)
assert (handles >= 0).all()

with MESH:
    search = D.make_search_step(MESH, CFG, k=10)
    queries = base[rng.integers(0, len(base), 64)] + 0.01 * rng.normal(
        size=(64, 16)
    ).astype(np.float32)
    alive = jnp.ones((4,), bool)
    d, v = search(stacked, jnp.asarray(queries), alive)
    d, v = np.asarray(d), np.asarray(v)

    # brute-force ground truth by handle
    bf = ((queries[:, None, :] - base[None]) ** 2).sum(-1)
    gt = handles[np.argsort(bf, axis=1)[:, :10]]
    hits = sum(
        len(set(gt[i].tolist()) & set(v[i].tolist())) for i in range(len(queries))
    )
    recall = hits / (len(queries) * 10)
    assert recall > 0.85, f"distributed recall {recall}"
    print(f"PASS distributed_search recall={recall:.3f}")

    # ---- distributed insert: new vectors become searchable ----
    insert = D.make_insert_step(MESH, CFG)
    new = make_clustered(rng, 32, 16, n_clusters=2)
    stacked, new_handles = insert(
        stacked, jnp.asarray(new), jnp.ones(len(new), bool)
    )
    new_handles = np.asarray(new_handles)
    assert (new_handles >= 0).all(), new_handles
    d2, v2 = search(stacked, jnp.asarray(new), alive)
    v2 = np.asarray(v2)
    found = sum(int(new_handles[i]) in v2[i].tolist() for i in range(32))
    assert found >= 30, f"only {found}/32 distributed inserts recalled"
    print(f"PASS distributed_insert found={found}/32")

    # owners spread across shards (centroid-space routing, clustered data)
    owners = np.unique(new_handles // CFG.num_vectors_cap)
    print(f"PASS insert_owners shards={owners.tolist()}")

    # ---- distributed delete ----
    delete = D.make_delete_step(MESH, CFG)
    stacked = delete(stacked, jnp.asarray(new_handles[:16]))
    d3, v3 = search(stacked, jnp.asarray(new[:16]), alive)
    v3 = np.asarray(v3)
    still = sum(int(new_handles[i]) in v3[i].tolist() for i in range(16))
    assert still == 0, f"{still} deleted handles still returned"
    print("PASS distributed_delete")

    # ---- maintenance step runs sharded ----
    maintain = D.make_maintenance_step(MESH, CFG)
    stacked, _did = maintain(stacked)
    print("PASS distributed_maintenance")

    # ---- shard-down graceful degradation ----
    alive_down = jnp.asarray([True, True, False, True])
    d4, v4 = search(stacked, jnp.asarray(queries), alive_down)
    v4 = np.asarray(v4)
    assert np.isfinite(np.asarray(d4)[v4 >= 0]).all()
    dead_shard_hits = ((v4 // CFG.num_vectors_cap) == 2) & (v4 >= 0)
    assert not dead_shard_hits.any(), "dead shard leaked results"
    hits4 = sum(
        len(set(gt[i].tolist()) & set(v4[i].tolist())) for i in range(len(queries))
    )
    recall4 = hits4 / (len(queries) * 10)
    assert recall4 > 0.45, f"degraded recall too low {recall4}"
    print(f"PASS shard_down degraded_recall={recall4:.3f} (full={recall:.3f})")

# ---- document-sharding over BOTH axes (8 shards, billion-scale layout) ----
stacked8, handles8 = D.build_sharded_state(CFG, base, 8)
with MESH:
    search8 = D.make_search_step(
        MESH, CFG, k=10, shard_axes=("data", "model"), probe_chunk=4
    )
    insert8 = D.make_insert_step(MESH, CFG, shard_axes=("data", "model"))
    d8, v8 = search8(stacked8, jnp.asarray(queries), jnp.ones((8,), bool))
    v8 = np.asarray(v8)
    gt8 = handles8[np.argsort(bf, axis=1)[:, :10]]
    hits8 = sum(
        len(set(gt8[i].tolist()) & set(v8[i].tolist())) for i in range(len(queries))
    )
    recall8 = hits8 / (len(queries) * 10)
    assert recall8 > 0.85, f"8-shard recall {recall8}"
    stacked8, h8 = insert8(stacked8, jnp.asarray(new), jnp.ones(len(new), bool))
    assert (np.asarray(h8) >= 0).all(), np.asarray(h8)
    print(f"PASS document_sharded_8 recall={recall8:.3f}")

# ---- elastic re-shard 4 -> 2 ----
restacked, handles2 = D.reshard(CFG, stacked, 4, 2)
MESH2 = jax.make_mesh((4, 2), ("data", "model"))
with MESH2:
    search2 = D.make_search_step(MESH2, CFG, k=10)
    d5, v5 = search2(restacked, jnp.asarray(queries), jnp.ones((2,), bool))
    assert (np.asarray(v5)[:, 0] >= 0).all()
    print("PASS elastic_reshard 4->2")

print("ALL_DISTRIBUTED_PASS")
