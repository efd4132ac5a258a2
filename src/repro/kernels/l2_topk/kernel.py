"""Fused L2-distance + per-tile k-min Pallas kernel (centroid navigation).

Computes ``d(q, c) = ||q||^2 - 2 q·c + ||c||^2`` for a (query-tile ×
centroid-tile) block on the MXU, then extracts the k smallest per query row
with an unrolled min/mask loop on the VPU, writing a per-tile candidate set.
The caller merges per-tile candidates with one final ``lax.top_k`` — a
two-stage tournament that never materializes the full (Q, P) distance matrix
in HBM (for P ~ 1e7 centroids per shard that matrix would be >GBs).

Masking: invalid centroids are encoded by the caller as ``c_sqn = +BIG`` so
no separate mask operand is needed in VMEM.

Tiling: queries (BQ, d), centroids (BP, d), ``d`` contracted in full (vector
dims ≤ a few hundred — fits VMEM comfortably: BQ=128, BP=512, d=128 f32 →
64 KB + 256 KB tiles).  MXU dims: (BQ×d)·(d×BP), all multiples of 128 when
padded by ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

# Plain Python float: a jnp scalar would be a captured traced constant,
# which pallas_call rejects.
BIG = 3.0e38


def _l2_topk_kernel(q_ref, c_ref, csq_ref, out_d_ref, out_i_ref, *, k: int,
                    block_p: int):
    pi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32)           # (BQ, d)
    c = c_ref[...].astype(jnp.float32)           # (BP, d)
    csq = csq_ref[...]                           # (1, BP) f32 (BIG if invalid)

    qsq = jnp.sum(q * q, axis=1, keepdims=True)  # (BQ, 1)
    cross = jax.lax.dot_general(
        q, c, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                            # (BQ, BP)
    d = qsq - 2.0 * cross + csq

    bq = d.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, block_p), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1)
    kd = jnp.zeros((bq, k), jnp.float32)
    ki = jnp.zeros((bq, k), jnp.int32)
    # Unrolled k-min extraction (k is small: nprobe candidates per tile);
    # the arg-min is the first column at the minimum, and the (BQ, k)
    # tile is assembled with selects and stored once.
    for j in range(k):
        m = jnp.min(d, axis=1, keepdims=True)
        a = jnp.min(jnp.where(d == m, col, block_p), axis=1, keepdims=True)
        kd = jnp.where(slot == j, m, kd)
        ki = jnp.where(slot == j, a, ki)
        d = jnp.where(col == a, BIG, d)
    out_d_ref[0] = kd
    out_i_ref[0] = ki + pi * block_p


@functools.partial(
    jax.jit,
    static_argnames=("k", "block_q", "block_p", "interpret"),
)
def l2_topk_tiles(
    queries: jax.Array,   # (Q, d) — Q multiple of block_q
    centroids: jax.Array,  # (P, d) — P multiple of block_p
    c_sqn: jax.Array,      # (1, P) f32, +BIG on invalid/padded centroids
    *,
    k: int,
    block_q: int = 128,
    block_p: int = 512,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Per-tile candidates: ``(dists (Q, T*k), indices (Q, T*k))`` where
    T = P/block_p.  Final global top-k is done by the caller.

    The kernel writes tile-major ``(T, Q, k)`` blocks — a ``(block_q, k)``
    block of a ``(Q, T*k)`` array would break the TPU's (8, 128) block
    rule whenever ``k`` is not a multiple of 128 — and the wrapper lays
    them out query-major afterwards."""
    q_n, dim = queries.shape
    p_n = centroids.shape[0]
    assert q_n % block_q == 0 and p_n % block_p == 0, (q_n, p_n)
    t = p_n // block_p

    kernel = functools.partial(_l2_topk_kernel, k=k, block_p=block_p)
    out_d, out_i = pl.pallas_call(
        kernel,
        grid=(q_n // block_q, t),
        in_specs=[
            pl.BlockSpec((block_q, dim), lambda qi, pi: (qi, 0)),
            pl.BlockSpec((block_p, dim), lambda qi, pi: (pi, 0)),
            pl.BlockSpec((1, block_p), lambda qi, pi: (0, pi)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, k), lambda qi, pi: (pi, qi, 0)),
            pl.BlockSpec((1, block_q, k), lambda qi, pi: (pi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, q_n, k), jnp.float32),
            jax.ShapeDtypeStruct((t, q_n, k), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(queries, centroids, c_sqn)
    return (
        out_d.transpose(1, 0, 2).reshape(q_n, t * k),
        out_i.transpose(1, 0, 2).reshape(q_n, t * k),
    )
