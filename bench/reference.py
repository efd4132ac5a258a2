"""The plain reference: exact k nearest neighbours by brute force.

Copied from the program's ``chip_smoke.py`` (``brute_force_topk``,
``recall_at_k``) and kept here, where the program cannot change it.  It
imports nothing of the program.  Vectors are int8, so the distances are
computed in int32 on the default JAX device (int8 products, int32 sums:
exact squared L2 for any d up to 2**31 / 2**16) and merged on the host.

``precision_bits=4`` gives the control: the same brute force over vectors
and queries cut to their top 4 bits (int4) — the step below the int8
payload the configurations state.
"""
from __future__ import annotations

import functools

import numpy as np

_FAR = np.iinfo(np.int32).max      # distance of a padded row


def quantize(x: np.ndarray, precision_bits: int) -> np.ndarray:
    """int8 values kept to ``precision_bits`` (8: unchanged), as int8."""
    x = np.asarray(x, np.int8)
    if precision_bits >= 8:
        return x
    step = 1 << (8 - precision_bits)
    return (np.floor_divide(x.astype(np.int32), step) * step).astype(np.int8)


@functools.lru_cache(maxsize=None)
def _chunk_topk(k: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(q, x, m):
        """The ``k`` smallest squared distances from each query to the
        first ``m`` rows of ``x``, nearest first, and their row numbers."""
        qi, xi = q.astype(jnp.int32), x.astype(jnp.int32)
        dot = jnp.dot(q, x.T, preferred_element_type=jnp.int32)
        d = (qi * qi).sum(1)[:, None] + (xi * xi).sum(1)[None, :] - 2 * dot
        d = jnp.where(jnp.arange(x.shape[0])[None, :] < m, d, _FAR)
        neg, j = jax.lax.top_k(-d, k)
        return -neg, j
    return run


def brute_force_topk(vecs: np.ndarray, ids: np.ndarray, queries: np.ndarray,
                     k: int, *, chunk: int = 65536,
                     precision_bits: int = 8) -> tuple[np.ndarray, np.ndarray]:
    """Exact ``k`` nearest rows of ``vecs (N, d)`` to each query by squared
    L2: ``(dists (Q, k) f64, ids (Q, k) i64)``, nearest first; ``-1``/inf
    pad when N < k.  Scans ``vecs`` in row chunks of one shape, each a
    top-k on the device, merged into a running top-k on the host."""
    q = quantize(queries, precision_bits)
    ids = np.asarray(ids, np.int64)
    best_d = np.full((len(q), k), _FAR, np.int64)
    best_i = np.full((len(q), k), -1, np.int64)
    # one shape for every chunk: the chunk, or the power of two that holds
    # a smaller set
    chunk = min(chunk, 1 << max(0, len(vecs) - 1).bit_length())
    kk = min(k, chunk)
    step = _chunk_topk(kk)
    for s in range(0, len(vecs), chunk):
        x = quantize(vecs[s:s + chunk], precision_bits)
        m = len(x)
        if m < chunk:
            x = np.concatenate([x, np.zeros((chunk - m, x.shape[1]), np.int8)])
        d, j = (np.asarray(a, np.int64) for a in step(q, x, m))
        cat_d = np.concatenate([best_d, d], 1)
        cat_i = np.concatenate(
            [best_i, np.where(j < m, ids[s + np.minimum(j, m - 1)], -1)], 1)
        sel = np.argsort(cat_d, axis=1, kind="stable")[:, :k]
        best_d = np.take_along_axis(cat_d, sel, 1)
        best_i = np.take_along_axis(cat_i, sel, 1)
    far = best_d >= _FAR
    best_i[far] = -1
    return np.where(far, np.inf, best_d.astype(np.float64)), best_i


def sq_dist(vecs: np.ndarray, queries: np.ndarray) -> np.ndarray:
    """Exact squared L2 between row ``i`` of ``vecs`` and of ``queries``."""
    diff = vecs.astype(np.float64) - queries.astype(np.float64)
    return np.einsum("ij,ij->i", diff, diff)


def recall_row(found_ids, found_exact_d, kth_d: float, valid) -> int:
    """Tie-aware hits of one answer row: distinct valid ids no farther
    than the reference's k-th distance.  ``found_exact_d`` holds the exact
    distance of each found id to the query; ``valid(id)`` says whether the
    id may be returned at all."""
    seen = set()
    hits = 0
    for vid, d in zip(found_ids.tolist(), found_exact_d.tolist()):
        if vid < 0 or vid in seen or not valid(vid):
            continue
        seen.add(vid)
        if d <= kth_d + 1e-6:
            hits += 1
    return hits
