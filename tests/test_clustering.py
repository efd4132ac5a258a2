import jax
import jax.numpy as jnp
import numpy as np

from repro.core.clustering import (
    balanced_kmeans,
    balanced_two_means,
    hierarchical_balanced_kmeans,
    pow2_bucket,
)
from repro.utils.compile_cache import CompileCounter
from tests.conftest import make_clustered


def test_balanced_kmeans_assigns_valid_only(rng):
    x = jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32))
    valid = jnp.asarray(np.arange(64) < 40)
    cen, assign = balanced_kmeans(jax.random.PRNGKey(0), x, valid, k=4)
    assign = np.asarray(assign)
    assert (assign[40:] == -1).all()
    assert set(np.unique(assign[:40])).issubset({0, 1, 2, 3})


def test_balanced_kmeans_balances(rng):
    # Heavily skewed data: one dense blob + sparse outliers.
    x = np.concatenate(
        [
            rng.normal(size=(90, 4)).astype(np.float32) * 0.01,
            rng.normal(size=(10, 4)).astype(np.float32) * 5 + 10,
        ]
    )
    cen, assign = balanced_kmeans(
        jax.random.PRNGKey(1), jnp.asarray(x), jnp.ones(100, bool),
        k=4, balance_weight=4.0, iters=20,
    )
    counts = np.bincount(np.asarray(assign), minlength=4)
    assert counts.max() <= 60, counts  # without penalty one cluster gets ~90


def test_two_means_halves(rng):
    x = jnp.asarray(make_clustered(rng, 100, 16, n_clusters=2))
    valid = jnp.ones(100, bool)
    cen, a = balanced_two_means(jax.random.PRNGKey(0), x, valid)
    a = np.asarray(a)
    n0, n1 = (a == 0).sum(), (a == 1).sum()
    assert n0 + n1 == 100
    assert abs(n0 - n1) <= 1  # hard rebalance to ceil(n/2)


def test_two_means_respects_mask(rng):
    x = jnp.asarray(rng.normal(size=(32, 8)).astype(np.float32))
    valid = jnp.asarray(np.arange(32) < 20)
    _, a = balanced_two_means(jax.random.PRNGKey(0), x, valid)
    a = np.asarray(a)
    assert (a[20:] == -1).all()
    assert ((a[:20] == 0) | (a[:20] == 1)).all()


def test_hierarchical_build_bounds_leaf_size(rng):
    x = make_clustered(rng, 2000, 16, n_clusters=10)
    cen, assign = hierarchical_balanced_kmeans(x, max_posting_size=64)
    counts = np.bincount(assign, minlength=cen.shape[0])
    assert counts.max() <= 64
    assert cen.shape[0] >= 2000 // 64
    # every vector assigned
    assert (assign >= 0).all() and assign.max() < cen.shape[0]


def test_hierarchical_build_degenerate_identical_points():
    x = np.ones((100, 8), np.float32)
    cen, assign = hierarchical_balanced_kmeans(x, max_posting_size=16)
    counts = np.bincount(assign, minlength=cen.shape[0])
    assert counts.sum() == 100


def test_pow2_bucket():
    assert [pow2_bucket(n) for n in (1, 64, 65, 1000, 1024, 1025)] == [
        64, 64, 128, 1024, 1024, 2048]


def test_hierarchical_build_compiles_per_bucket_not_per_node(rng):
    """Nodes are padded to power-of-two buckets, so a build compiles
    about one program per (bucket, k) pair — not one per node size (a
    20k-row build has hundreds of distinct node sizes)."""
    x = make_clustered(rng, 20000, 16, n_clusters=64, spread=0.3)
    jax.clear_caches()
    with CompileCounter() as cc:
        cen, assign = hierarchical_balanced_kmeans(x, max_posting_size=40)
    assert np.bincount(assign).max() <= 40
    assert cen.shape[0] >= 20000 // 40
    assert cc.compiles <= 40, cc.compiles
