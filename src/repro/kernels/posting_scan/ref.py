"""Pure-jnp oracles for the posting_scan kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def scan_posting_blocks_ref(
    block_table: jax.Array, queries: jax.Array, blocks: jax.Array
) -> jax.Array:
    """(Q, NB, BS) distances — per-query page scan."""
    gathered = blocks[block_table]                 # (Q, NB, BS, d)
    q = queries.astype(jnp.float32)[:, None, None, :]
    diff = gathered.astype(jnp.float32) - q
    return jnp.sum(diff * diff, axis=-1)


def scan_unique_blocks_ref(
    unique_blocks: jax.Array, queries: jax.Array, blocks: jax.Array
) -> jax.Array:
    """(NB, BS, Q) distances — batched unique-page scan, page-major."""
    gathered = blocks[unique_blocks].astype(jnp.float32)  # (NB, BS, d)
    q = queries.astype(jnp.float32)
    diff = gathered[:, :, None, :] - q[None, None, :, :]
    return jnp.sum(diff * diff, axis=-1)


def _kmin_ref(d: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Row-wise k smallest of ``d (..., cols)`` with index-order tie-break
    (matches the kernels' min/mask loop)."""
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx.astype(jnp.int32)


def _kmin_pages_ref(d: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """`_kmin_ref` over the slot axis of page-major ``d (NB, BS, Q)`` →
    ``(NB, k, Q)``."""
    kd, ki = _kmin_ref(jnp.swapaxes(d, 1, 2), k)
    return jnp.swapaxes(kd, 1, 2), jnp.swapaxes(ki, 1, 2)


def scan_per_query_topk_ref(
    block_table: jax.Array, queries: jax.Array, blocks: jax.Array,
    slot_bias: jax.Array, k: int,
) -> tuple[jax.Array, jax.Array]:
    """(Q, NB, k) per-page k-min candidates — per-query schedule."""
    d = scan_posting_blocks_ref(block_table, queries, blocks) + slot_bias
    return _kmin_ref(d, k)


def scan_batched_topk_ref(
    unique_blocks: jax.Array, queries: jax.Array, blocks: jax.Array,
    slot_bias: jax.Array, k: int,
) -> tuple[jax.Array, jax.Array]:
    """(NB, k, Q) per-(page, query) k-min candidates — batched schedule."""
    d = scan_unique_blocks_ref(unique_blocks, queries, blocks)
    d = d + slot_bias[:, :, None]
    return _kmin_pages_ref(d, k)


def scan_per_query_topk_q8_ref(
    block_table: jax.Array, queries: jax.Array, blocks: jax.Array,
    slot_bias: jax.Array, page_sz: jax.Array, k: int,
) -> tuple[jax.Array, jax.Array]:
    """Dequant-fused per-query oracle: reconstruct ``code*scale+zero``
    per page ((Q, NB, 2) params) before the distance math."""
    g = blocks[block_table].astype(jnp.float32)           # (Q, NB, BS, d)
    g = g * page_sz[..., 0][:, :, None, None] + page_sz[..., 1][:, :, None, None]
    q = queries.astype(jnp.float32)[:, None, None, :]
    diff = g - q
    d = jnp.sum(diff * diff, axis=-1) + slot_bias
    return _kmin_ref(d, k)


def scan_batched_topk_q8_ref(
    unique_blocks: jax.Array, queries: jax.Array, blocks: jax.Array,
    slot_bias: jax.Array, page_sz: jax.Array, k: int,
) -> tuple[jax.Array, jax.Array]:
    """Dequant-fused batched oracle ((NB, 2) per-unique-page params)."""
    g = blocks[unique_blocks].astype(jnp.float32)         # (NB, BS, d)
    g = g * page_sz[:, 0][:, None, None] + page_sz[:, 1][:, None, None]
    q = queries.astype(jnp.float32)
    diff = g[:, :, None, :] - q[None, None, :, :]
    d = jnp.sum(diff * diff, axis=-1) + slot_bias[:, :, None]
    return _kmin_pages_ref(d, k)
