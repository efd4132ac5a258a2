"""Balanced clustering: SPANN index build + LIRE split primitive.

Two entry points:

* :func:`balanced_kmeans` — fixed-iteration Lloyd with a size-penalty term,
  the JAX adaptation of SPANN's multi-constraint balanced clustering [67].
  Fully jittable (fixed shapes, ``fori_loop``), supports a validity mask so
  it can run over fixed-capacity posting buffers.
* :func:`hierarchical_balanced_kmeans` — host-driven recursive splitter used
  for the *offline* index build: split until every leaf fits
  ``max_posting_size``, returning centroids + assignments.  The per-node work
  is the jitted :func:`balanced_kmeans`; the recursion is host-side because
  build is offline (paper builds the base index offline too).

The LIRE *split* op uses ``balanced_kmeans(k=2)`` — the paper's "multi-
constraint balanced clustering ... to generate high-quality centroids and
balanced postings" (§4.2.1) specialized to a single oversized posting.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distance import MASK_DISTANCE, pairwise_sql2

Array = jax.Array


@functools.partial(
    jax.jit, static_argnames=("k", "iters", "balance_weight")
)
def balanced_kmeans(
    key: Array,
    x: Array,
    valid: Array,
    *,
    k: int,
    iters: int = 10,
    balance_weight: float = 1.0,
) -> tuple[Array, Array]:
    """Size-penalized Lloyd over the ``valid`` rows of ``x (n, d)``.

    Assignment cost for cluster c is ``sql2(x, centroid_c) + λ·size_c·mean_d``
    where ``size_c`` is the running cluster size from the previous iteration
    (SPANN's balance constraint as a Lagrangian penalty; λ=balance_weight).

    Returns ``(centroids (k, d) f32, assign (n,) i32)``; invalid rows get
    assignment ``-1``.
    """
    n, d = x.shape
    xf = x.astype(jnp.float32)
    validf = valid.astype(jnp.float32)
    n_valid = jnp.maximum(jnp.sum(validf), 1.0)

    # Init: k distinct valid points (gumbel-top-k over the validity mask).
    g = jax.random.gumbel(key, (n,))
    scores = jnp.where(valid, g, -jnp.inf)
    _, init_idx = jax.lax.top_k(scores, k)
    centroids0 = xf[init_idx]

    # Mean pairwise scale for the penalty: use mean squared norm spread.
    mean_sq = jnp.sum(jnp.sum(xf * xf, axis=-1) * validf) / n_valid

    def assign_step(centroids, sizes):
        dists = pairwise_sql2(xf, centroids)  # (n, k)
        penalty = balance_weight * (sizes / n_valid) * (mean_sq + 1e-6)
        cost = dists + penalty[None, :]
        a = jnp.argmin(cost, axis=-1).astype(jnp.int32)
        return jnp.where(valid, a, -1)

    def update_centroids(assign, centroids):
        onehot = jax.nn.one_hot(assign, k, dtype=jnp.float32)  # (-1 -> zeros)
        counts = jnp.sum(onehot, axis=0)  # (k,)
        sums = jnp.einsum("nk,nd->kd", onehot, xf)
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        # Keep old centroid if a cluster emptied out.
        new = jnp.where((counts > 0)[:, None], new, centroids)
        return new, counts

    def body(_, carry):
        centroids, sizes = carry
        a = assign_step(centroids, sizes)
        centroids, counts = update_centroids(a, centroids)
        return centroids, counts

    init_sizes = jnp.zeros((k,), jnp.float32)
    centroids, sizes = jax.lax.fori_loop(
        0, iters, body, (centroids0, init_sizes)
    )
    assign = assign_step(centroids, sizes)
    # Final centroid refresh so returned centroids match the assignment.
    centroids, _ = update_centroids(assign, centroids)
    return centroids, assign


def pow2_bucket(n: int) -> int:
    """Smallest power of two ``>= max(n, 64)``: the padded row count that
    bounds how many shapes a host-driven build compiles for."""
    return max(64, 1 << max(0, int(n) - 1).bit_length())


def hierarchical_balanced_kmeans(
    x: np.ndarray,
    *,
    max_posting_size: int,
    branch: int = 8,
    iters: int = 10,
    balance_weight: float = 1.0,
    seed: int = 0,
) -> tuple[np.ndarray, np.ndarray]:
    """Offline SPANN-style build: recursively split until every leaf fits.

    Returns ``(centroids (P, d) f32, assign (n,) i32)`` with
    ``max leaf size <= max_posting_size`` (up to degenerate duplicates).
    Host-driven recursion over jitted :func:`balanced_kmeans`.  Each
    node's rows are padded on the host to a power-of-two bucket and
    masked by ``valid``, so the build compiles one program per
    ``(bucket, k)`` pair rather than one per node size.
    """
    x = np.asarray(x, np.float32)
    n = x.shape[0]
    assign = np.zeros((n,), np.int32)
    centroids: list[np.ndarray] = []
    key = jax.random.PRNGKey(seed)

    # Work stack of index arrays into x.
    stack: list[np.ndarray] = [np.arange(n, dtype=np.int64)]
    guard = 0
    while stack:
        guard += 1
        if guard > 16 * max(1, n // max(1, max_posting_size)) + 64:
            # Degenerate data (e.g. all-identical points): stop splitting.
            for idx in stack:
                cid = len(centroids)
                centroids.append(x[idx].mean(axis=0))
                assign[idx] = cid
            break
        idx = stack.pop()
        if idx.size <= max_posting_size:
            cid = len(centroids)
            centroids.append(
                x[idx].mean(axis=0) if idx.size else np.zeros(x.shape[1], np.float32)
            )
            assign[idx] = cid
            continue
        k = min(branch, max(2, int(np.ceil(idx.size / max_posting_size))))
        key, sub = jax.random.split(key)
        bucket = pow2_bucket(idx.size)
        sub_x = np.zeros((bucket, x.shape[1]), np.float32)
        sub_x[: idx.size] = x[idx]
        _, a = balanced_kmeans(
            sub, sub_x, np.arange(bucket) < idx.size,
            k=k, iters=iters, balance_weight=balance_weight,
        )
        a = np.asarray(a)[: idx.size]
        split_happened = False
        for c in range(k):
            child = idx[a == c]
            if child.size == 0:
                continue
            if child.size < idx.size:
                split_happened = True
            stack.append(child)
        if not split_happened:
            # k-means failed to split (identical points): force halve.
            stack.pop()  # remove the re-pushed full set
            half = idx.size // 2
            stack.append(idx[:half])
            stack.append(idx[half:])
    return np.stack(centroids, axis=0), assign


@functools.partial(jax.jit, static_argnames=("iters",))
def balanced_two_means(
    key: Array, x: Array, valid: Array, *, iters: int = 8
) -> tuple[Array, Array]:
    """LIRE split primitive: balanced 2-means over a posting buffer.

    ``x (L, d)`` is the (garbage-collected) posting contents with validity
    mask ``valid (L,)``.  Returns ``(centroids (2, d), assign (L,) in
    {-1,0,1})``.  Balance is enforced *hard* at the end: if one side exceeds
    ``ceil(n_valid/2) + slack`` the farthest-from-centroid excess vectors are
    flipped, matching the paper's "evenly splits the oversized posting into
    two smaller ones" (§3.2).
    """
    L, d = x.shape
    centroids, assign = balanced_kmeans(
        key, x, valid, k=2, iters=iters, balance_weight=2.0
    )
    # Hard rebalance: compute signed preference and flip the worst offenders.
    xf = x.astype(jnp.float32)
    d0 = jnp.sum((xf - centroids[0]) ** 2, axis=-1)
    d1 = jnp.sum((xf - centroids[1]) ** 2, axis=-1)
    pref = d0 - d1  # >0 means prefers cluster 1
    a = jnp.where(pref > 0, 1, 0).astype(jnp.int32)
    a = jnp.where(valid, a, -1)
    n_valid = jnp.sum(valid)
    target = (n_valid + 1) // 2

    def flip_excess(a):
        n1 = jnp.sum(a == 1)
        n0 = jnp.sum(a == 0)
        # margin of moving to the other side; flip smallest margins first.
        margin = jnp.abs(pref)
        # excess on side 1 -> flip to 0 those with smallest margin.
        def flip(a, from_side, count):
            cand = (a == from_side)
            score = jnp.where(cand, -margin, -jnp.inf)
            # top-|count| smallest margins among cand
            order = jnp.argsort(-score)  # descending score = ascending margin
            ranks = jnp.zeros((L,), jnp.int32).at[order].set(
                jnp.arange(L, dtype=jnp.int32)
            )
            to_flip = cand & (ranks < count)
            return jnp.where(to_flip, 1 - from_side, a)

        a = jax.lax.cond(
            n1 > target, lambda a: flip(a, 1, n1 - target), lambda a: a, a
        )
        n0 = jnp.sum(a == 0)
        a = jax.lax.cond(
            n0 > target, lambda a: flip(a, 0, n0 - target), lambda a: a, a
        )
        return a

    a = flip_excess(a)
    # Refresh centroids to match the final assignment.
    onehot = jax.nn.one_hot(a, 2, dtype=jnp.float32)
    counts = jnp.sum(onehot, axis=0)
    sums = jnp.einsum("nk,nd->kd", onehot, xf)
    centroids = sums / jnp.maximum(counts, 1.0)[:, None]
    return centroids, a
