"""Public wrappers for the posting-scan kernels.

These integrate the BlockPool with the Pallas kernels: build the block
table from posting ids, clamp absent pages to page 0, and mask distances of
invalid/stale slots to +BIG for the downstream top-k.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.posting_scan import kernel as K

BIG = jnp.float32(3.0e38)


@functools.partial(jax.jit, static_argnames=("interpret",))
def scan_posting_blocks(
    queries: jax.Array,       # (Q, d)
    posting_blocks: jax.Array,  # (P_cap, MB) i32 block table rows
    pids: jax.Array,          # (Q, nprobe) probed postings (-1 = none)
    blocks: jax.Array,        # (B, BS, d)
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Per-query paged scan.  Returns ``(dists (Q, nprobe*MB*BS), flat_slot
    (Q, nprobe*MB*BS) bool valid-page mask)`` — caller applies vid/version
    masks and top-k."""
    q_n = queries.shape[0]
    bs = blocks.shape[1]
    table = posting_blocks[jnp.maximum(pids, 0)]        # (Q, nprobe, MB)
    table = jnp.where(pids[..., None] >= 0, table, -1)
    flat = table.reshape(q_n, -1)                       # (Q, NB)
    page_ok = flat >= 0
    d = K.scan_per_query(
        jnp.maximum(flat, 0), queries, blocks, interpret=interpret
    )                                                   # (Q, NB, BS)
    d = jnp.where(page_ok[:, :, None], d, BIG)
    return d.reshape(q_n, -1), jnp.repeat(page_ok, bs, axis=1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def scan_unique_blocks(
    queries: jax.Array,      # (Q, d)
    unique_blocks: jax.Array,  # (NB,) i32, -1 = padding
    blocks: jax.Array,       # (B, BS, d)
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Batch-dedup scan.  Returns dists (NB, BS, Q) with padded pages = BIG."""
    ok = unique_blocks >= 0
    d = K.scan_batched(
        jnp.maximum(unique_blocks, 0), queries, blocks, interpret=interpret
    )
    return jnp.where(ok[:, None, None], d, BIG)


# ---------------------------------------------------------------------------
# Fused per-page top-k wrappers + batch page dedup (the search hot path)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def scan_posting_blocks_topk(
    queries: jax.Array,      # (Q, d)
    page_table: jax.Array,   # (Q, NB) i32 block ids, -1 = absent/not probed
    slot_live: jax.Array,    # (Q, NB, BS) bool — live slots of each page
    blocks: jax.Array,       # (B, BS, d)
    *,
    k: int,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Per-query paged scan with fused per-page k-min.

    Returns ``(dists (Q, NB, k), slots (Q, NB, k))``; dead candidates
    (absent page or dead slot) carry dist >= BIG."""
    bias = jnp.where(
        slot_live & (page_table >= 0)[:, :, None], jnp.float32(0), BIG
    )
    return K.scan_per_query_topk(
        jnp.maximum(page_table, 0), queries, blocks, bias,
        k=k, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def scan_unique_blocks_topk(
    queries: jax.Array,       # (Q, d)
    unique_blocks: jax.Array,  # (NB,) i32, -1 = padding
    slot_live: jax.Array,     # (NB, BS) bool — live slots of each page
    blocks: jax.Array,        # (B, BS, d)
    *,
    k: int,
    n_pages: jax.Array | None = None,  # () i32 — leading rows to scan
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Batch-dedup paged scan with fused per-(page, query) k-min.

    Returns ``(dists (NB, k, Q), slots (NB, k, Q))``.  With ``n_pages``
    (the dedup's distinct-page count) the rows from it on are not
    scanned: they hold an empty page's candidates, as -1 rows do."""
    bias = jnp.where(
        slot_live & (unique_blocks >= 0)[:, None], jnp.float32(0), BIG
    )
    return K.scan_batched_topk(
        jnp.maximum(unique_blocks, 0), queries, blocks, bias,
        k=k, n_pages=n_pages, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def scan_posting_blocks_topk_q8(
    queries: jax.Array,      # (Q, d)
    page_table: jax.Array,   # (Q, NB) i32 block ids, -1 = absent/not probed
    slot_live: jax.Array,    # (Q, NB, BS) bool — live slots of each page
    blocks: jax.Array,       # (B, BS, d) int8 codes
    page_scale: jax.Array,   # (Q, NB) f32 — per-page posting scale
    page_zero: jax.Array,    # (Q, NB) f32 — per-page posting zero-point
    *,
    k: int,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """`scan_posting_blocks_topk` over int8 codes: the per-page scale/zero
    ride the DMA and the page is dequantized inside the kernel."""
    bias = jnp.where(
        slot_live & (page_table >= 0)[:, :, None], jnp.float32(0), BIG
    )
    page_sz = jnp.stack(
        [page_scale.astype(jnp.float32), page_zero.astype(jnp.float32)],
        axis=-1,
    )                                                   # (Q, NB, 2)
    return K.scan_per_query_topk_q8(
        jnp.maximum(page_table, 0), queries, blocks, bias, page_sz,
        k=k, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def scan_unique_blocks_topk_q8(
    queries: jax.Array,       # (Q, d)
    unique_blocks: jax.Array,  # (NB,) i32, -1 = padding
    slot_live: jax.Array,     # (NB, BS) bool — live slots of each page
    blocks: jax.Array,        # (B, BS, d) int8 codes
    page_scale: jax.Array,    # (NB,) f32 — per-unique-page posting scale
    page_zero: jax.Array,     # (NB,) f32 — per-unique-page zero-point
    *,
    k: int,
    n_pages: jax.Array | None = None,  # () i32 — leading rows to scan
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """`scan_unique_blocks_topk` over int8 codes with in-kernel dequant."""
    bias = jnp.where(
        slot_live & (unique_blocks >= 0)[:, None], jnp.float32(0), BIG
    )
    page_sz = jnp.stack(
        [page_scale.astype(jnp.float32), page_zero.astype(jnp.float32)],
        axis=-1,
    )                                                   # (NB, 2)
    return K.scan_batched_topk_q8(
        jnp.maximum(unique_blocks, 0), queries, blocks, bias, page_sz,
        k=k, n_pages=n_pages, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("budget", "num_blocks"))
def dedup_pages(
    pages: jax.Array,         # (N,) i32 probed block ids, -1 = invalid
    *,
    budget: int,
    num_blocks: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fixed-shape batch page dedup (the batched schedule's compaction).

    Returns ``(unique (budget,), member_pos (N,), n_unique (), overflow ())``:

    * ``unique`` — sorted distinct valid page ids, -1-padded; when more
      than ``budget`` distinct pages were probed, the *highest-numbered*
      pages are dropped (jnp.unique keeps the smallest ``budget``).
    * ``member_pos`` — for every input probe, the row of ``unique``
      holding its page (clipped; -1 where the probe is invalid or its
      page was dropped by the budget).
    * ``n_unique`` / ``overflow`` — distinct valid pages probed, and how
      many of them the budget dropped (the recall-accounting signal).
    """
    sentinel = jnp.int32(num_blocks)  # > every real page id
    flat = jnp.where(pages >= 0, pages, sentinel)
    # ONE sort serves both the unique compaction and the distinct count
    # (jnp.unique would sort a second time just to recount)
    srt = jnp.sort(flat)
    first = jnp.concatenate([jnp.ones((1,), bool), srt[1:] != srt[:-1]])
    first = first & (srt < sentinel)
    n_unique = jnp.sum(first)
    (pos,) = jnp.nonzero(first, size=budget, fill_value=0)
    kept = jnp.minimum(n_unique, budget)
    uniq = jnp.where(jnp.arange(budget) < kept, srt[pos], sentinel)
    uniq_valid = uniq < sentinel
    overflow = jnp.maximum(n_unique - kept, 0)
    # membership: searchsorted into the sorted unique rows
    pos = jnp.searchsorted(uniq, flat).astype(jnp.int32)
    pos = jnp.minimum(pos, budget - 1)
    hit = (uniq[pos] == flat) & (pages >= 0)
    member_pos = jnp.where(hit, pos, -1)
    return jnp.where(uniq_valid, uniq, -1), member_pos, n_unique, overflow
