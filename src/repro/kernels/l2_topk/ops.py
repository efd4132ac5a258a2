"""Public jit'd wrapper for the l2_topk kernel: padding, masking, final merge."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.l2_topk.kernel import BIG, l2_topk_tiles


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(
    jax.jit, static_argnames=("k", "block_q", "block_p", "interpret")
)
def l2_topk(
    queries: jax.Array,    # (Q, d)
    centroids: jax.Array,  # (P, d)
    valid: jax.Array,      # (P,) bool
    *,
    k: int,
    block_q: int = 128,
    block_p: int = 512,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Masked k-nearest centroids: ``(dists (Q,k), idx (Q,k))``.

    Two-stage tournament: per-tile k-min in the Pallas kernel, then one
    ``lax.top_k`` over the T*k survivors.  Correct because the global top-k
    is a subset of the union of per-tile top-k sets.
    """
    q_n, dim = queries.shape
    p_n = centroids.shape[0]
    block_q = min(block_q, _round_up(q_n, 8))
    block_p = min(block_p, _round_up(p_n, 128))
    qp = _round_up(q_n, block_q)
    pp = _round_up(p_n, block_p)
    k_tile = min(k, block_p)

    qpad = jnp.pad(queries, ((0, qp - q_n), (0, 0)))
    cpad = jnp.pad(centroids, ((0, pp - p_n), (0, 0)))
    csq = jnp.sum(centroids.astype(jnp.float32) ** 2, axis=1)
    csq = jnp.where(valid, csq, BIG)
    csq = jnp.pad(csq, (0, pp - p_n), constant_values=BIG)[None, :]

    tile_d, tile_i = l2_topk_tiles(
        qpad, cpad, csq, k=k_tile, block_q=block_q, block_p=block_p,
        interpret=interpret,
    )
    # Final merge over per-tile candidates.
    neg, sel = jax.lax.top_k(-tile_d, k)
    dists = -neg
    idx = jnp.take_along_axis(tile_i, sel, axis=1)
    idx = jnp.where(dists < BIG / 2, idx, -1)
    dists = jnp.maximum(dists, 0.0)
    return dists[:q_n], idx[:q_n]
