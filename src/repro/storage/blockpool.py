"""Paged block pool — the TPU-native analogue of SPFresh's Block Controller.

Paper §4.3: postings live on raw SSD blocks; an in-memory *Block Mapping*
maps posting id → block offsets; a *Free Block Pool* recycles blocks; APPEND
touches only the posting's tail block; PUT bulk-writes a posting.

Here the "SSD" is a fixed-capacity HBM array ``blocks[B_cap, BS, d]`` and the
block mapping is ``posting_blocks[P_cap, MB]`` (int32 block ids, -1 unused).
GET is a block-table gather (the same indirection as paged-attention KV);
APPEND is a dynamic-update of a single (block, slot); the free pool is an
int32 stack.  Everything is functional: each op returns a new pool pytree.

Blocks carry payload + metadata per slot, mirroring the paper's on-disk tuple
``<vector id, version number, raw vector>``.

Dirty tracking (paper §4.4, the block controller's copy-on-write ledger):
``dirty[B_cap]`` marks every block whose payload or slot metadata changed
since the last checkpoint cleared it.  All write paths set it — APPEND
tail writes, PUT rewrites, GC write-backs, and block frees (a freed
block's cleared ``block_vid`` must reach the next delta snapshot too).
``storage.snapshot`` serializes only dirty blocks into delta snapshots,
making checkpoint bytes proportional to churn instead of capacity.

Tiered payload (``storage.codec``): the hot tier ``blocks`` stores the
scan payload in the codec's dtype (fp32 passthrough / bf16 / int8 with
per-posting ``post_scale``/``post_zero``); lossy codecs additionally
carry a cold exact-fp32 tier ``blocks_exact`` (same geometry, same dirty
bitmap) that serves maintenance reads and the search rerank.  Every
write path encodes into the hot tier and mirrors raw fp32 into the cold
tier; PUT retrains the posting's scale/zero from the rows it writes,
APPEND reuses the posting's current parameters (first-ever append
trains them from that row).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.storage import codec as pc
from repro.utils.tree import field, pytree_dataclass

Array = jax.Array


@pytree_dataclass
class BlockPool:
    # --- static geometry ---
    block_size: int = field(static=True)           # BS vectors per block
    max_blocks_per_posting: int = field(static=True)  # MB
    codec: str = field(static=True)                # fp32 | bf16 | int8
    # --- device state ---
    blocks: Array        # (B_cap, BS, d) hot-tier payload (codec dtype)
    block_vid: Array     # (B_cap, BS) i32 vector ids, -1 empty
    block_ver: Array     # (B_cap, BS) u8 version written with the data
    posting_blocks: Array  # (P_cap, MB) i32 block ids, -1 unused
    posting_len: Array     # (P_cap,) i32 vectors in posting
    free_stack: Array      # (B_cap,) i32 free block ids (top at index free_top-1)
    free_top: Array        # () i32 number of free blocks
    dirty: Array           # (B_cap,) bool — block changed since last checkpoint
    post_scale: Array      # (P_cap,) f32 per-posting quant scale (1 untrained)
    post_zero: Array       # (P_cap,) f32 per-posting quant zero-point
    blocks_exact: Array | None  # (B_cap, BS, d) f32 cold tier (lossy codecs)

    @property
    def posting_capacity(self) -> int:
        return self.block_size * self.max_blocks_per_posting

    @property
    def num_postings_cap(self) -> int:
        return self.posting_blocks.shape[0]

    @property
    def num_blocks_cap(self) -> int:
        return self.blocks.shape[0]

    @property
    def dim(self) -> int:
        return self.blocks.shape[-1]


def make_block_pool(
    *,
    num_blocks: int,
    block_size: int,
    dim: int,
    num_postings_cap: int,
    max_blocks_per_posting: int,
    dtype=jnp.float32,
    codec: str = "fp32",
) -> BlockPool:
    """Fresh, empty pool: every block free, every posting empty.

    ``dtype`` is the *configured* vector dtype; the hot-tier payload is
    stored at ``codec.payload_dtype(codec, dtype)`` and lossy codecs get
    a cold exact-fp32 tier alongside.
    """
    pay = pc.payload_dtype(codec, dtype)
    return BlockPool(
        block_size=block_size,
        max_blocks_per_posting=max_blocks_per_posting,
        codec=codec,
        blocks=jnp.zeros((num_blocks, block_size, dim), pay),
        block_vid=jnp.full((num_blocks, block_size), -1, jnp.int32),
        block_ver=jnp.zeros((num_blocks, block_size), jnp.uint8),
        posting_blocks=jnp.full(
            (num_postings_cap, max_blocks_per_posting), -1, jnp.int32
        ),
        posting_len=jnp.zeros((num_postings_cap,), jnp.int32),
        free_stack=jnp.arange(num_blocks, dtype=jnp.int32),
        free_top=jnp.asarray(num_blocks, jnp.int32),
        dirty=jnp.zeros((num_blocks,), bool),
        post_scale=jnp.ones((num_postings_cap,), jnp.float32),
        post_zero=jnp.zeros((num_postings_cap,), jnp.float32),
        blocks_exact=(
            jnp.zeros((num_blocks, block_size, dim), jnp.float32)
            if pc.has_exact_tier(codec)
            else None
        ),
    )


def _encode_rows(pool: BlockPool, vecs: Array, scale, zero) -> Array:
    """fp32 rows -> hot-tier payload under (scale, zero) (broadcasting)."""
    return pc.encode_payload(pool.codec, vecs, scale, zero, pool.blocks.dtype)


def clear_dirty(pool: BlockPool) -> BlockPool:
    """All blocks clean — called after a checkpoint serializes the pool."""
    return pool.replace(dirty=jnp.zeros_like(pool.dirty))


# ---------------------------------------------------------------------------
# Block allocation
# ---------------------------------------------------------------------------

def _alloc_block(pool: BlockPool) -> tuple[BlockPool, Array]:
    """Pop a free block; returns (pool, block_id) with block_id = -1 on OOM."""
    has = pool.free_top > 0
    top = jnp.maximum(pool.free_top - 1, 0)
    bid = jnp.where(has, pool.free_stack[top], -1)
    pool = pool.replace(free_top=jnp.where(has, top, pool.free_top))
    return pool, bid


def _free_block(pool: BlockPool, bid: Array) -> BlockPool:
    """Push a block back (no-op for bid < 0). Clears slot metadata."""
    do = bid >= 0
    safe = jnp.maximum(bid, 0)
    free_stack = jnp.where(
        do,
        pool.free_stack.at[pool.free_top].set(bid.astype(jnp.int32)),
        pool.free_stack,
    )
    block_vid = jnp.where(
        do, pool.block_vid.at[safe].set(-1), pool.block_vid
    )
    dirty = jnp.where(do, pool.dirty.at[safe].set(True), pool.dirty)
    return pool.replace(
        free_stack=free_stack,
        free_top=jnp.where(do, pool.free_top + 1, pool.free_top),
        block_vid=block_vid,
        dirty=dirty,
    )


# ---------------------------------------------------------------------------
# APPEND — tail-block read-modify-write (paper §4.3)
# ---------------------------------------------------------------------------

def append_one(
    pool: BlockPool, pid: Array, vec: Array, vid: Array, ver: Array, enable: Array
) -> tuple[BlockPool, Array]:
    """Append one vector to posting ``pid``. Returns (pool, ok).

    ok=False when the posting is at capacity or the pool is out of blocks;
    the caller (Updater) counts drops — in production the shard would spill
    to a sibling replica, here we surface it as a statistic.
    """
    length = pool.posting_len[pid]
    slot = jnp.remainder(length, pool.block_size)
    blk_idx = length // pool.block_size
    need_new = (slot == 0)
    full = blk_idx >= pool.max_blocks_per_posting
    can = enable & (~full)

    # Allocate only when needed; otherwise keep pool untouched.
    def with_alloc(pool):
        pool2, bid = _alloc_block(pool)
        return pool2, bid

    def no_alloc(pool):
        safe_idx = jnp.minimum(blk_idx, pool.max_blocks_per_posting - 1)
        return pool, pool.posting_blocks[pid, safe_idx]

    pool, bid = jax.lax.cond(can & need_new, with_alloc, no_alloc, pool)
    ok = can & (bid >= 0)
    safe_bid = jnp.maximum(bid, 0)
    safe_idx = jnp.minimum(blk_idx, pool.max_blocks_per_posting - 1)

    posting_blocks = jnp.where(
        ok & need_new,
        pool.posting_blocks.at[pid, safe_idx].set(bid.astype(jnp.int32)),
        pool.posting_blocks,
    )
    # First-ever append trains the posting's quant params from this row;
    # later appends reuse them (out-of-range values clip — the exact tier
    # plus rerank bound the damage until the next PUT retrains).
    fresh = ok & (length == 0)
    scale0, zero0 = pc.train_scale_zero(vec[None, :], jnp.ones((1,), bool))
    scale = jnp.where(fresh, scale0, pool.post_scale[pid])
    zero = jnp.where(fresh, zero0, pool.post_zero[pid])
    post_scale = jnp.where(
        fresh, pool.post_scale.at[pid].set(scale0), pool.post_scale
    )
    post_zero = jnp.where(
        fresh, pool.post_zero.at[pid].set(zero0), pool.post_zero
    )
    blocks = jnp.where(
        ok,
        pool.blocks.at[safe_bid, slot].set(_encode_rows(pool, vec, scale, zero)),
        pool.blocks,
    )
    blocks_exact = pool.blocks_exact
    if blocks_exact is not None:
        blocks_exact = jnp.where(
            ok,
            blocks_exact.at[safe_bid, slot].set(vec.astype(jnp.float32)),
            blocks_exact,
        )
    block_vid = jnp.where(
        ok, pool.block_vid.at[safe_bid, slot].set(vid.astype(jnp.int32)),
        pool.block_vid,
    )
    block_ver = jnp.where(
        ok, pool.block_ver.at[safe_bid, slot].set(ver.astype(jnp.uint8)),
        pool.block_ver,
    )
    posting_len = jnp.where(
        ok, pool.posting_len.at[pid].add(1), pool.posting_len
    )
    dirty = jnp.where(ok, pool.dirty.at[safe_bid].set(True), pool.dirty)
    return (
        pool.replace(
            blocks=blocks,
            blocks_exact=blocks_exact,
            block_vid=block_vid,
            block_ver=block_ver,
            posting_blocks=posting_blocks,
            posting_len=posting_len,
            dirty=dirty,
            post_scale=post_scale,
            post_zero=post_zero,
        ),
        ok,
    )


@jax.jit
def append_batch(
    pool: BlockPool,
    pids: Array,
    vecs: Array,
    vids: Array,
    vers: Array,
    enable: Array,
) -> tuple[BlockPool, Array]:
    """Sequential batched append (appends can collide on a posting's tail).

    ``lax.scan`` over the batch; each step is O(1) state surgery, mirroring
    the paper's per-request APPEND path.  Returns (pool, ok_mask).
    """

    def step(pool, args):
        pid, vec, vid, ver, en = args
        pool, ok = append_one(pool, pid, vec, vid, ver, en)
        return pool, ok

    pool, oks = jax.lax.scan(step, pool, (pids, vecs, vids, vers, enable))
    return pool, oks


@jax.jit
def append_scatter(
    pool: BlockPool,
    pids: Array,
    vecs: Array,
    vids: Array,
    vers: Array,
    enable: Array,
) -> tuple[BlockPool, Array]:
    """Vectorized batched APPEND: n rows land in ONE scatter instead of an
    n-step ``lax.scan`` — the fused-reassignment append of the maintenance
    round (and its merge moves), where the scan's per-row sequential cost
    would swamp the batching win.

    Rows targeting the same posting are ranked in row order (earlier rows
    win tail slots — the same landed set as `append_batch`); a row fails
    (``ok=False``) when its posting is at capacity.  Tail blocks for every
    boundary-crossing posting are allocated in one cumsum-indexed pop;
    under pool OOM the rows needing fresh blocks fail as a group, so each
    posting still lands a contiguous rank prefix (`append_batch` fails
    them one by one — the failure set can differ only when the free pool
    runs dry mid-batch).
    """
    n = pids.shape[0]
    bs = pool.block_size
    cap = pool.posting_capacity
    mb = pool.max_blocks_per_posting
    nb_cap = pool.num_blocks_cap
    p_cap = pool.num_postings_cap
    en = enable & (pids >= 0)
    safe = jnp.maximum(pids, 0).astype(jnp.int32)

    # Rank of each enabled row within its posting, preserving row order:
    # stable group-by-pid sort, then position minus group start.
    row = jnp.arange(n, dtype=jnp.int32)
    spid_key = jnp.where(en, safe, p_cap)
    order = jnp.lexsort((row, spid_key))
    sp = spid_key[order]
    pos = jnp.arange(n, dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), sp[1:] != sp[:-1]])
    start = jax.lax.cummax(jnp.where(first, pos, 0))
    rank = jnp.zeros((n,), jnp.int32).at[order].set(pos - start)

    slot_g = pool.posting_len[safe] + rank
    ok_cap = en & (slot_g < cap)
    blk = slot_g // bs
    slot = slot_g % bs
    safe_blk = jnp.minimum(blk, mb - 1)
    existing = pool.posting_blocks[safe, safe_blk]       # (n,)

    # One leader row per absent tail block (ranks are contiguous, so every
    # block boundary has a slot==0 row); allocate all leaders at once.
    leader = ok_cap & (slot == 0) & (existing < 0)
    n_new = jnp.sum(leader)
    have = n_new <= pool.free_top
    lrank = jnp.cumsum(leader.astype(jnp.int32)) - 1
    lpos = pool.free_top - 1 - lrank
    new_bid = jnp.where(
        leader & have, pool.free_stack[jnp.clip(lpos, 0, nb_cap - 1)], -1
    )
    posting_blocks = pool.posting_blocks.at[
        jnp.where(leader & have, safe, p_cap), safe_blk
    ].set(new_bid, mode="drop")

    bid = jnp.where(existing >= 0, existing, posting_blocks[safe, safe_blk])
    ok = ok_cap & (bid >= 0)

    tb = jnp.where(ok, bid, nb_cap)
    # Rows landing in a previously-empty posting (global slot 0) train its
    # quant params from their own row; later ranks of the same posting in
    # this batch read the freshly scattered value.
    fresh = ok & (slot_g == 0)
    rs, rz = pc.train_scale_zero(
        vecs[:, None, :], jnp.ones((n, 1), bool)
    )                                                    # (n,) per-row
    post_scale = pool.post_scale.at[
        jnp.where(fresh, safe, p_cap)
    ].set(rs, mode="drop")
    post_zero = pool.post_zero.at[
        jnp.where(fresh, safe, p_cap)
    ].set(rz, mode="drop")
    blocks = pool.blocks.at[tb, slot].set(
        _encode_rows(
            pool, vecs, post_scale[safe][:, None], post_zero[safe][:, None]
        ),
        mode="drop",
    )
    blocks_exact = pool.blocks_exact
    if blocks_exact is not None:
        blocks_exact = blocks_exact.at[tb, slot].set(
            vecs.astype(jnp.float32), mode="drop"
        )
    block_vid = pool.block_vid.at[tb, slot].set(
        vids.astype(jnp.int32), mode="drop"
    )
    block_ver = pool.block_ver.at[tb, slot].set(
        vers.astype(jnp.uint8), mode="drop"
    )
    posting_len = pool.posting_len.at[jnp.where(ok, safe, p_cap)].add(
        1, mode="drop"
    )
    dirty = pool.dirty.at[tb].set(True, mode="drop")
    return (
        pool.replace(
            blocks=blocks,
            blocks_exact=blocks_exact,
            block_vid=block_vid,
            block_ver=block_ver,
            posting_blocks=posting_blocks,
            posting_len=posting_len,
            free_top=pool.free_top - jnp.where(have, n_new, 0),
            dirty=dirty,
            post_scale=post_scale,
            post_zero=post_zero,
        ),
        ok,
    )


# ---------------------------------------------------------------------------
# GET — block-table gather (ParallelGET is vmap of this)
# ---------------------------------------------------------------------------

def gather_posting(
    pool: BlockPool, pid: Array
) -> tuple[Array, Array, Array, Array]:
    """Read a whole posting into fixed-capacity buffers.

    Returns ``(vecs (MB*BS, d), vids (MB*BS,), vers (MB*BS,), valid (MB*BS,))``.
    Slots past ``posting_len`` are masked invalid.  Lossy codecs serve
    the cold exact tier so maintenance rewrites never accumulate
    requantization error.
    """
    bids = pool.posting_blocks[pid]  # (MB,)
    safe = jnp.maximum(bids, 0)
    payload = pool.blocks_exact if pool.blocks_exact is not None else pool.blocks
    vids = pool.block_vid[safe]
    vers = pool.block_ver[safe]
    cap = pool.posting_capacity
    d = pool.dim
    # gathered as rows of the flat (B·BS, d) pool, not as (MB, BS, d)
    # pages: the TPU compiler fuses page-shaped and posting-shaped
    # converts of one int8 page gather and then aborts (fusion_util
    # TransformWindow)
    bs = pool.block_size
    rows = (safe[:, None] * bs + jnp.arange(bs, dtype=jnp.int32)).reshape(cap)
    vecs = payload.reshape(-1, d)[rows]
    vids = vids.reshape(cap)
    vers = vers.reshape(cap)
    idx = jnp.arange(cap, dtype=jnp.int32)
    valid = (idx < pool.posting_len[pid]) & (vids >= 0)
    return vecs, vids, vers, valid


def gather_posting_hot(
    pool: BlockPool, pid: Array
) -> tuple[Array, Array, Array, Array]:
    """`gather_posting`, but decoding the HOT tier (codec payload).

    The oracle search path uses this so its distances match what the
    dequant-fused Pallas scan computes — bit-for-bit the same decoded
    values, never the exact tier (which only the rerank reads).
    """
    bids = pool.posting_blocks[pid]  # (MB,)
    safe = jnp.maximum(bids, 0)
    vecs = pc.decode_payload(
        pool.codec, pool.blocks[safe], pool.post_scale[pid], pool.post_zero[pid]
    )
    vids = pool.block_vid[safe]
    vers = pool.block_ver[safe]
    cap = pool.posting_capacity
    d = pool.dim
    vecs = vecs.reshape(cap, d)
    vids = vids.reshape(cap)
    vers = vers.reshape(cap)
    idx = jnp.arange(cap, dtype=jnp.int32)
    valid = (idx < pool.posting_len[pid]) & (vids >= 0)
    return vecs, vids, vers, valid


def parallel_get(
    pool: BlockPool, pids: Array
) -> tuple[Array, Array, Array, Array]:
    """Paper's ParallelGET: batched posting fetch, ``pids (m,)`` →
    ``(m, MB*BS, ...)`` buffers."""
    return jax.vmap(lambda p: gather_posting(pool, p))(pids)


def parallel_get_hot(
    pool: BlockPool, pids: Array
) -> tuple[Array, Array, Array, Array]:
    """Batched `gather_posting_hot` — the oracle search path's fetch."""
    return jax.vmap(lambda p: gather_posting_hot(pool, p))(pids)


def gather_postings(
    pool: BlockPool, pids: Array
) -> tuple[Array, Array, Array, Array]:
    """Multi-pid bulk GET for the maintenance round: ``pids (k,)`` →
    ``(vecs (k, MB*BS, d), vids, vers, valid)``.  Negative pids read
    posting 0 but the caller's enable masks make those rows inert."""
    return parallel_get(pool, jnp.maximum(pids, 0))


def gather_posting_ids(
    pool: BlockPool, pid: Array
) -> tuple[Array, Array, Array]:
    """Metadata-only posting read: ``(vids, vers, valid)`` without payload.

    Used by the reassign NPA re-check (does a live replica already exist in
    the target posting?) where fetching vector payloads would be wasted HBM
    traffic.
    """
    bids = pool.posting_blocks[pid]
    safe = jnp.maximum(bids, 0)
    vids = pool.block_vid[safe].reshape(-1)
    vers = pool.block_ver[safe].reshape(-1)
    idx = jnp.arange(pool.posting_capacity, dtype=jnp.int32)
    valid = (idx < pool.posting_len[pid]) & (vids >= 0)
    return vids, vers, valid


# ---------------------------------------------------------------------------
# PUT / DELETE — bulk posting rewrite and free
# ---------------------------------------------------------------------------

def free_posting(pool: BlockPool, pid: Array, enable: Array) -> BlockPool:
    """Release all blocks of ``pid`` to the free pool and empty it."""
    bids = pool.posting_blocks[pid]  # (MB,)

    def step(pool, bid):
        pool = jax.lax.cond(
            enable & (bid >= 0), lambda p: _free_block(p, bid), lambda p: p, pool
        )
        return pool, ()

    pool, _ = jax.lax.scan(step, pool, bids)
    posting_blocks = jnp.where(
        enable, pool.posting_blocks.at[pid].set(-1), pool.posting_blocks
    )
    posting_len = jnp.where(
        enable, pool.posting_len.at[pid].set(0), pool.posting_len
    )
    post_scale = jnp.where(
        enable, pool.post_scale.at[pid].set(1.0), pool.post_scale
    )
    post_zero = jnp.where(
        enable, pool.post_zero.at[pid].set(0.0), pool.post_zero
    )
    return pool.replace(
        posting_blocks=posting_blocks,
        posting_len=posting_len,
        post_scale=post_scale,
        post_zero=post_zero,
    )


def free_postings(pool: BlockPool, pids: Array, enable: Array) -> BlockPool:
    """Batched `free_posting`: release all blocks of ``k`` DISTINCT postings
    in ONE scatter (the maintenance round's retire/GC path).

    The per-block ``lax.scan`` of `free_posting` becomes a cumsum-indexed
    push: every freed block id lands in ``free_stack[free_top + i]`` where
    ``i`` is its rank among the round's freed blocks; disabled rows and
    absent blocks scatter out of bounds and are dropped.
    """
    enable = enable & (pids >= 0)
    safe = jnp.maximum(pids, 0)
    bids = pool.posting_blocks[safe]                     # (k, MB)
    do = enable[:, None] & (bids >= 0)
    flat_bids = bids.reshape(-1)
    flat_do = do.reshape(-1)
    nb_cap = pool.num_blocks_cap

    pos = pool.free_top + jnp.cumsum(flat_do.astype(jnp.int32)) - 1
    free_stack = pool.free_stack.at[jnp.where(flat_do, pos, nb_cap)].set(
        flat_bids, mode="drop"
    )
    block_vid = pool.block_vid.at[
        jnp.where(flat_do, flat_bids, nb_cap)
    ].set(-1, mode="drop")
    dirty = pool.dirty.at[
        jnp.where(flat_do, flat_bids, nb_cap)
    ].set(True, mode="drop")
    row = jnp.where(enable, safe, pool.num_postings_cap)
    posting_blocks = pool.posting_blocks.at[row].set(-1, mode="drop")
    posting_len = pool.posting_len.at[row].set(0, mode="drop")
    post_scale = pool.post_scale.at[row].set(1.0, mode="drop")
    post_zero = pool.post_zero.at[row].set(0.0, mode="drop")
    return pool.replace(
        free_stack=free_stack,
        free_top=pool.free_top + jnp.sum(flat_do),
        block_vid=block_vid,
        posting_blocks=posting_blocks,
        posting_len=posting_len,
        dirty=dirty,
        post_scale=post_scale,
        post_zero=post_zero,
    )


def put_postings(
    pool: BlockPool,
    pids: Array,
    vecs: Array,
    vids: Array,
    vers: Array,
    ns: Array,
    enable: Array,
) -> tuple[BlockPool, Array]:
    """Batched `put_posting`: bulk-write ``k`` DISTINCT postings in ONE
    scatter — the maintenance round's half-writes and GC write-backs.

    ``vecs (k, cap, d)`` / ``vids`` / ``vers (k, cap)`` are fixed-capacity
    buffers; row ``j`` writes its first ``ns[j]`` entries.  Per row the
    semantics match `put_posting`: old blocks freed first, ``ceil(n/BS)``
    fresh blocks allocated (LIFO from the shared stack), payload written,
    length set.  Allocation is first-come: once cumulative demand exceeds
    the free pool, that row and all later enabled rows fail (``ok=False``,
    posting left empty — same observable outcome as `put_posting` under
    pool OOM; the drain loop retries next round).
    """
    k, cap, _ = vecs.shape
    assert cap == pool.posting_capacity, (cap, pool.posting_capacity)
    mb, bs = pool.max_blocks_per_posting, pool.block_size
    nb_cap = pool.num_blocks_cap

    enable = enable & (pids >= 0)
    safe = jnp.maximum(pids, 0)
    pool = free_postings(pool, pids, enable)

    need = jnp.where(enable, (ns + bs - 1) // bs, 0)     # (k,)
    ok = enable & (jnp.cumsum(need) <= pool.free_top)
    used = jnp.where(ok, need, 0)
    off = jnp.cumsum(used) - used                        # exclusive

    i_idx = jnp.arange(mb, dtype=jnp.int32)[None, :]     # (1, MB)
    in_use = ok[:, None] & (i_idx < need[:, None])       # (k, MB)
    pos = pool.free_top - 1 - (off[:, None] + i_idx)     # LIFO pop order
    bids = jnp.where(
        in_use, pool.free_stack[jnp.clip(pos, 0, nb_cap - 1)], -1
    )

    # PUT retrains each posting's quant params from the rows it writes.
    row_valid = (
        jnp.arange(cap, dtype=jnp.int32)[None, :] < ns[:, None]
    )                                                    # (k, cap)
    scale, zero = pc.train_scale_zero(vecs, row_valid)   # (k,)
    enc = _encode_rows(pool, vecs, scale[:, None, None], zero[:, None, None])
    vecs_b = enc.reshape(k, mb, bs, -1)
    vids_b = vids.reshape(k, mb, bs)
    vers_b = vers.reshape(k, mb, bs)
    in_range = (
        i_idx[..., None] * bs + jnp.arange(bs, dtype=jnp.int32)[None, None, :]
    ) < ns[:, None, None]                                # (k, MB, BS)
    tgt = jnp.where(in_use, bids, nb_cap).reshape(-1)
    blocks = pool.blocks.at[tgt].set(
        vecs_b.reshape(k * mb, bs, -1), mode="drop"
    )
    blocks_exact = pool.blocks_exact
    if blocks_exact is not None:
        blocks_exact = blocks_exact.at[tgt].set(
            vecs.astype(jnp.float32).reshape(k * mb, bs, -1), mode="drop"
        )
    block_vid = pool.block_vid.at[tgt].set(
        jnp.where(in_range, vids_b, -1).reshape(k * mb, bs), mode="drop"
    )
    block_ver = pool.block_ver.at[tgt].set(
        jnp.where(in_range, vers_b, jnp.uint8(0)).reshape(k * mb, bs),
        mode="drop",
    )

    row = jnp.where(ok, safe, pool.num_postings_cap)
    posting_blocks = pool.posting_blocks.at[
        jnp.broadcast_to(row[:, None], (k, mb)),
        jnp.broadcast_to(i_idx, (k, mb)),
    ].set(bids, mode="drop")
    posting_len = pool.posting_len.at[row].set(
        ns.astype(jnp.int32), mode="drop"
    )
    post_scale = pool.post_scale.at[row].set(scale, mode="drop")
    post_zero = pool.post_zero.at[row].set(zero, mode="drop")
    dirty = pool.dirty.at[tgt].set(True, mode="drop")
    return (
        pool.replace(
            blocks=blocks,
            blocks_exact=blocks_exact,
            block_vid=block_vid,
            block_ver=block_ver,
            posting_blocks=posting_blocks,
            posting_len=posting_len,
            free_top=pool.free_top - jnp.sum(used),
            dirty=dirty,
            post_scale=post_scale,
            post_zero=post_zero,
        ),
        ok,
    )


def put_posting(
    pool: BlockPool,
    pid: Array,
    vecs: Array,
    vids: Array,
    vers: Array,
    n: Array,
    enable: Array,
) -> tuple[BlockPool, Array]:
    """Bulk-write a posting (paper PUT): free old blocks, allocate
    ``ceil(n/BS)`` fresh ones, write payload, set length.

    ``vecs (cap, d)`` etc. are fixed-capacity buffers; only the first ``n``
    entries are meaningful.  Returns (pool, ok).
    """
    cap = vecs.shape[0]
    assert cap == pool.posting_capacity, (cap, pool.posting_capacity)
    pool = free_posting(pool, pid, enable)
    n_blocks_needed = (n + pool.block_size - 1) // pool.block_size
    have = pool.free_top >= n_blocks_needed
    ok = enable & have

    bs = pool.block_size
    row_valid = jnp.arange(cap, dtype=jnp.int32) < n
    scale, zero = pc.train_scale_zero(vecs, row_valid)
    enc = _encode_rows(pool, vecs, scale, zero)
    exact = vecs.astype(jnp.float32)
    enc = enc.reshape(pool.max_blocks_per_posting, bs, -1)
    exact = exact.reshape(pool.max_blocks_per_posting, bs, -1)
    vids = vids.reshape(pool.max_blocks_per_posting, bs)
    vers = vers.reshape(pool.max_blocks_per_posting, bs)

    def step(carry, i):
        pool = carry

        def write(pool):
            pool2, bid = _alloc_block(pool)
            safe = jnp.maximum(bid, 0)
            slot_idx = jnp.arange(bs, dtype=jnp.int32)
            in_range = (i * bs + slot_idx) < n
            blocks = pool2.blocks.at[safe].set(
                jnp.where(in_range[:, None], enc[i], pool2.blocks[safe])
            )
            blocks_exact = pool2.blocks_exact
            if blocks_exact is not None:
                blocks_exact = blocks_exact.at[safe].set(
                    jnp.where(in_range[:, None], exact[i], blocks_exact[safe])
                )
            block_vid = pool2.block_vid.at[safe].set(
                jnp.where(in_range, vids[i], -1)
            )
            block_ver = pool2.block_ver.at[safe].set(
                jnp.where(in_range, vers[i], 0)
            )
            posting_blocks = pool2.posting_blocks.at[pid, i].set(bid)
            return pool2.replace(
                blocks=blocks,
                blocks_exact=blocks_exact,
                block_vid=block_vid,
                block_ver=block_ver,
                posting_blocks=posting_blocks,
                dirty=pool2.dirty.at[safe].set(True),
            )

        pool = jax.lax.cond(ok & (i < n_blocks_needed), write, lambda p: p, pool)
        return pool, ()

    pool, _ = jax.lax.scan(
        step, pool, jnp.arange(pool.max_blocks_per_posting, dtype=jnp.int32)
    )
    posting_len = jnp.where(
        ok, pool.posting_len.at[pid].set(n.astype(jnp.int32)), pool.posting_len
    )
    post_scale = jnp.where(
        ok, pool.post_scale.at[pid].set(scale), pool.post_scale
    )
    post_zero = jnp.where(
        ok, pool.post_zero.at[pid].set(zero), pool.post_zero
    )
    return (
        pool.replace(
            posting_len=posting_len,
            post_scale=post_scale,
            post_zero=post_zero,
        ),
        ok,
    )


def used_blocks(pool: BlockPool) -> Array:
    """Number of allocated blocks (for resource accounting, paper Fig. 7d)."""
    return pool.num_blocks_cap - pool.free_top
