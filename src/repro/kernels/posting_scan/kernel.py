"""Paged posting-scan Pallas kernels (block-table indirection).

Two variants of the same hot loop — compute query↔vector distances for
vectors that live in SSD-block-sized pages of the BlockPool, addressed
through a block table (exactly the paged-attention KV indirection):

* ``scan_kernel_per_query`` — the paper-faithful ParallelGET schedule: each
  grid step streams one page of one query's probed posting from HBM to VMEM
  and emits that query's distances.  HBM traffic = Q * nprobe * page bytes.

* ``scan_kernel_batched`` — beyond-paper batch-dedup schedule: the caller
  dedups the pages probed by the *whole query batch*; each unique page is
  streamed ONCE and scored against all Q queries with one (Q×d)·(d×BS) MXU
  GEMM.  HBM traffic divides by the average probe multiplicity.

Both use ``PrefetchScalarGridSpec`` so the block table is available to the
BlockSpec index_map (the indirection happens in the DMA engine, not in the
kernel body).

Each variant also has a ``*_topk`` form that fuses the per-page reduce: the
kernel takes a per-slot distance bias (0 live / +BIG dead — absent page,
empty slot, stale version, deletion) and emits only the ``k`` smallest
candidates of each (page, query) tile with an unrolled min/mask loop, the
same VPU idiom as ``l2_topk``.  The caller's merge works over
``(Q, NB·k)`` candidates instead of the full ``(Q, NB·BS)`` distance
matrix, which is what lets the search hot path stream pages without ever
materializing the distance tiles in HBM.

The batched ``*_topk`` forms also take the count of pages that hold work
(the dedup's distinct pages), scalar-prefetched next to the page ids: a
grid step at or past it fetches nothing, computes nothing and writes the
empty page's candidates, so a grid sized for the bucket's worst case costs
little more than the pages the batch really probed.

Layout: the TPU compiler requires the last two dims of every block to be
divisible by (8, 128) or equal to the array's.  Operands whose per-step
row block would be 1 (a query row, a page's bias row, its [scale, zero]
pair, a per-query output tile) are therefore reshaped by the wrappers to
carry a singleton second-to-last axis, so each block's trailing dims
equal the array's.  The reshapes are free and the public shapes are
unchanged.  Page norms are taken on the MXU (a ones-row GEMM) so that
they come out lane-major, next to the (rows, BS) cross term.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

# Plain Python float: a jnp scalar would be a captured traced constant,
# which pallas_call rejects (same trick as l2_topk).
BIG = 3.0e38

_HI = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))   # contract the last dims: (m, d)·(n, d)ᵀ
_SMEM_TABLE_BYTES = 256 * 1024


def _dot_nt(a, b, *, in_order: bool):
    """``a (m, d) · b (n, d)ᵀ`` at full f32 precision.

    On the MXU an entry's sum over d does not depend on how many rows
    ``a`` has (measured on a v5e: one query row and 128 give the same
    bits), but swapping the operands changes it.  XLA's CPU dot depends
    on the shapes too (one row or many) and on what it fuses around the
    dot, so under interpretation the per-query and batched schedules
    would differ in the last bits.  ``in_order`` (set when a kernel is
    interpreted) sums the products one coordinate after another instead,
    the same order for every shape."""
    if not in_order:
        return jax.lax.dot_general(
            a, b, _NT, precision=_HI, preferred_element_type=jnp.float32
        )

    def add_coordinate(j, acc):
        aj = jax.lax.dynamic_slice_in_dim(a, j, 1, axis=1)     # (m, 1)
        bj = jax.lax.dynamic_slice_in_dim(b, j, 1, axis=1)     # (n, 1)
        return acc + aj * bj.T

    acc = jnp.zeros((a.shape[0], b.shape[0]), jnp.float32)
    return jax.lax.fori_loop(0, a.shape[1], add_coordinate, acc)


def _page_dists(q, b, *, in_order: bool):
    """Squared L2 ``(rows, BS)`` between queries ``q (rows, d)`` and one
    page ``b (BS, d)``, both f32: ‖q‖² − 2 q·b + ‖b‖² clamped at 0.
    Both schedules call it with the queries on the left of every dot,
    one row or a batch, so they agree bit for bit; the batched kernels
    transpose its result to page-major ``(BS, rows)``, which puts a
    batch of queries along the lanes."""
    ones = jnp.ones((1, q.shape[1]), jnp.float32)
    qsq = _dot_nt(q * q, ones, in_order=in_order)              # (rows, 1)
    bsq = _dot_nt(ones, b * b, in_order=in_order)              # (1, BS)
    cross = _dot_nt(q, b, in_order=in_order)
    return jnp.maximum(qsq - 2.0 * cross + bsq, 0.0)


def _kmin(d, *, k: int, axis: int):
    """Unrolled k-min of ``d`` along ``axis``: the l2_topk min/mask loop.
    Returns ``(dists, argmins)`` with ``axis`` cut to ``k``; the arg-min
    is the first index holding the minimum (``jnp.argmin``'s tie rule).
    Results are assembled with selects so the kernel stores whole
    blocks."""
    n = d.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, d.shape, axis)
    out_shape = d.shape[:axis] + (k,) + d.shape[axis + 1:]
    slot = jax.lax.broadcasted_iota(jnp.int32, out_shape, axis)
    kd = jnp.zeros(out_shape, jnp.float32)
    ki = jnp.zeros(out_shape, jnp.int32)
    for j in range(k):
        m = jnp.min(d, axis=axis, keepdims=True)
        a = jnp.min(jnp.where(d == m, idx, n), axis=axis, keepdims=True)
        kd = jnp.where(slot == j, m, kd)
        ki = jnp.where(slot == j, a, ki)
        d = jnp.where(idx == a, BIG, d)
    return kd, ki


def _dequant(codes, sz):
    """One int8 code page ``(BS, d)`` decoded on the VPU with its
    posting's ``sz (1, 2)`` = [scale, zero]."""
    return codes.astype(jnp.float32) * sz[:, 0:1] + sz[:, 1:2]


def _by_query_chunks(call, block_table, queries, blocks, *row_operands):
    """Run a per-query-schedule ``call(table, queries, blocks, *rows)``
    once per query chunk and concatenate its outputs along the query
    axis.  The ``(Q, NB)`` block table is a scalar-prefetch operand and
    SMEM holds 1 MiB, so each call takes at most ``_SMEM_TABLE_BYTES`` of
    table.  ``row_operands`` are per-query and sliced with the table."""
    q_n, nb = block_table.shape
    rows = max(1, _SMEM_TABLE_BYTES // (4 * nb))
    outs = [
        call(block_table[s:s + rows], queries[s:s + rows], blocks,
             *(x[s:s + rows] for x in row_operands))
        for s in range(0, q_n, rows)
    ]
    if len(outs) == 1:
        return outs[0]
    return jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs), *outs)


def _scan_per_query_kernel(table_ref, q_ref, blk_ref, out_ref, *, in_order):
    # q_ref: (1, 1, d); blk_ref: (1, BS, d); out: (1, 1, 1, BS)
    q = q_ref[0].astype(jnp.float32)               # (1, d)
    b = blk_ref[0].astype(jnp.float32)             # (BS, d)
    out_ref[0, 0] = _page_dists(q, b, in_order=in_order)


def _scan_per_query_rows(block_table, queries, blocks, *, interpret):
    q_n, nb = block_table.shape
    _, bs, dim = blocks.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(q_n, nb),
        in_specs=[
            pl.BlockSpec((1, 1, dim), lambda q, j, table: (q, 0, 0)),
            pl.BlockSpec((1, bs, dim), lambda q, j, table: (table[q, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, bs), lambda q, j, table: (q, j, 0, 0)),
    )
    interpret = resolve_interpret(interpret)
    out = pl.pallas_call(
        functools.partial(_scan_per_query_kernel, in_order=interpret),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((q_n, nb, 1, bs), jnp.float32),
        interpret=interpret,
    )(block_table, queries[:, None, :], blocks)
    return out.reshape(q_n, nb, bs)


@functools.partial(
    jax.jit, static_argnames=("interpret",)
)
def scan_per_query(
    block_table: jax.Array,  # (Q, NB) i32 — block pool indices (clamped >=0)
    queries: jax.Array,      # (Q, d)
    blocks: jax.Array,       # (B, BS, d) — the block pool payload
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Distances (Q, NB, BS): page j of query q scored against query q."""
    return _by_query_chunks(
        functools.partial(_scan_per_query_rows, interpret=interpret),
        block_table, queries, blocks,
    )


def _scan_batched_kernel(ids_ref, q_ref, blk_ref, out_ref, *, in_order):
    # q_ref: (Q, d) resident; blk_ref: (1, BS, d); out: (1, BS, Q)
    q = q_ref[...].astype(jnp.float32)            # (Q, d)
    b = blk_ref[0].astype(jnp.float32)            # (BS, d)
    out_ref[0] = _page_dists(q, b, in_order=in_order).T


@functools.partial(
    jax.jit, static_argnames=("interpret",)
)
def scan_batched(
    unique_blocks: jax.Array,  # (NB,) i32 unique block pool indices
    queries: jax.Array,        # (Q, d)
    blocks: jax.Array,         # (B, BS, d)
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Distances (NB, BS, Q): each unique page scored against ALL queries
    (page-major, queries along the lanes)."""
    nb = unique_blocks.shape[0]
    q_n, dim = queries.shape
    _, bs, _ = blocks.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((q_n, dim), lambda i, ids: (0, 0)),
            pl.BlockSpec((1, bs, dim), lambda i, ids: (ids[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, q_n), lambda i, ids: (i, 0, 0)),
    )
    interpret = resolve_interpret(interpret)
    return pl.pallas_call(
        functools.partial(_scan_batched_kernel, in_order=interpret),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, bs, q_n), jnp.float32),
        interpret=interpret,
    )(unique_blocks, queries, blocks)


# ---------------------------------------------------------------------------
# Fused per-page top-k variants (streaming running-top-k reduce)
# ---------------------------------------------------------------------------

def _scan_per_query_topk_kernel(
    table_ref, q_ref, blk_ref, bias_ref, out_d_ref, out_i_ref, *, k: int,
    in_order: bool,
):
    # q_ref: (1, 1, d); blk_ref: (1, BS, d); bias_ref: (1, 1, 1, BS) f32
    # (0 live, +BIG dead); out: (1, 1, 1, k) dists + slot indices within
    # the page.
    q = q_ref[0].astype(jnp.float32)              # (1, d)
    b = blk_ref[0].astype(jnp.float32)            # (BS, d)
    d = _page_dists(q, b, in_order=in_order) + bias_ref[0, 0]  # (1, BS)
    kd, ki = _kmin(d, k=k, axis=1)                # (1, k)
    out_d_ref[0, 0] = kd
    out_i_ref[0, 0] = ki


def _scan_per_query_topk_rows(block_table, queries, blocks, slot_bias, *,
                              k, interpret):
    q_n, nb = block_table.shape
    _, bs, dim = blocks.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(q_n, nb),
        in_specs=[
            pl.BlockSpec((1, 1, dim), lambda q, j, table: (q, 0, 0)),
            pl.BlockSpec((1, bs, dim), lambda q, j, table: (table[q, j], 0, 0)),
            pl.BlockSpec((1, 1, 1, bs), lambda q, j, table: (q, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, k), lambda q, j, table: (q, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, k), lambda q, j, table: (q, j, 0, 0)),
        ],
    )
    interpret = resolve_interpret(interpret)
    out_d, out_i = pl.pallas_call(
        functools.partial(
            _scan_per_query_topk_kernel, k=k, in_order=interpret
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((q_n, nb, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((q_n, nb, 1, k), jnp.int32),
        ],
        interpret=interpret,
    )(block_table, queries[:, None, :], blocks, slot_bias[:, :, None, :])
    return out_d.reshape(q_n, nb, k), out_i.reshape(q_n, nb, k)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def scan_per_query_topk(
    block_table: jax.Array,  # (Q, NB) i32 — block pool indices (clamped >=0)
    queries: jax.Array,      # (Q, d)
    blocks: jax.Array,       # (B, BS, d)
    slot_bias: jax.Array,    # (Q, NB, BS) f32 — 0 live, +BIG dead
    *,
    k: int,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Per-query paged scan with fused per-page k-min.

    Returns ``(dists (Q, NB, k), slots (Q, NB, k))`` where ``slots`` index
    into the page (0..BS); dead candidates carry dist >= BIG."""
    assert k <= blocks.shape[1], (k, blocks.shape)
    return _by_query_chunks(
        functools.partial(_scan_per_query_topk_rows, k=k, interpret=interpret),
        block_table, queries, blocks, slot_bias,
    )


def _scan_per_query_topk_q8_kernel(
    table_ref, q_ref, blk_ref, bias_ref, sz_ref, out_d_ref, out_i_ref, *,
    k: int, in_order: bool,
):
    # Dequant-fused variant: blk_ref holds int8 codes; sz_ref (1, 1, 1, 2)
    # carries the page's posting [scale, zero], riding the block-table DMA
    # exactly like the liveness bias — the page streams at 1 byte/dim and
    # is reconstructed on the VPU before the distance math.
    q = q_ref[0].astype(jnp.float32)              # (1, d)
    b = _dequant(blk_ref[0], sz_ref[0, 0])        # (BS, d)
    d = _page_dists(q, b, in_order=in_order) + bias_ref[0, 0]  # (1, BS)
    kd, ki = _kmin(d, k=k, axis=1)                # (1, k)
    out_d_ref[0, 0] = kd
    out_i_ref[0, 0] = ki


def _scan_per_query_topk_q8_rows(block_table, queries, blocks, slot_bias,
                                 page_sz, *, k, interpret):
    q_n, nb = block_table.shape
    _, bs, dim = blocks.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(q_n, nb),
        in_specs=[
            pl.BlockSpec((1, 1, dim), lambda q, j, table: (q, 0, 0)),
            pl.BlockSpec((1, bs, dim), lambda q, j, table: (table[q, j], 0, 0)),
            pl.BlockSpec((1, 1, 1, bs), lambda q, j, table: (q, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, 2), lambda q, j, table: (q, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, k), lambda q, j, table: (q, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, k), lambda q, j, table: (q, j, 0, 0)),
        ],
    )
    interpret = resolve_interpret(interpret)
    out_d, out_i = pl.pallas_call(
        functools.partial(
            _scan_per_query_topk_q8_kernel, k=k, in_order=interpret
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((q_n, nb, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((q_n, nb, 1, k), jnp.int32),
        ],
        interpret=interpret,
    )(block_table, queries[:, None, :], blocks, slot_bias[:, :, None, :],
      page_sz[:, :, None, :])
    return out_d.reshape(q_n, nb, k), out_i.reshape(q_n, nb, k)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def scan_per_query_topk_q8(
    block_table: jax.Array,  # (Q, NB) i32 — block pool indices (clamped >=0)
    queries: jax.Array,      # (Q, d)
    blocks: jax.Array,       # (B, BS, d) int8 codes
    slot_bias: jax.Array,    # (Q, NB, BS) f32 — 0 live, +BIG dead
    page_sz: jax.Array,      # (Q, NB, 2) f32 — per-page [scale, zero]
    *,
    k: int,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Per-query paged scan over int8 codes with in-kernel dequant.

    Same contract as `scan_per_query_topk`; distances are computed on the
    reconstructed ``code * scale + zero`` values."""
    assert k <= blocks.shape[1], (k, blocks.shape)
    return _by_query_chunks(
        functools.partial(
            _scan_per_query_topk_q8_rows, k=k, interpret=interpret
        ),
        block_table, queries, blocks, slot_bias, page_sz,
    )


def _page_topk_or_empty(n_ref, out_d_ref, out_i_ref, topk):
    """Grid step ``i`` of a batched top-k kernel: below the count of pages
    holding work ``n_ref[0]``, store ``topk()``'s ``(k, Q)`` candidates;
    at or past it, store an empty page's — every distance BIG, slots
    ``0..k-1`` — which is what the k-min of an all-BIG page gives."""
    i = pl.program_id(0)

    @pl.when(i < n_ref[0])
    def _():
        kd, ki = topk()
        out_d_ref[0] = kd
        out_i_ref[0] = ki

    @pl.when(i >= n_ref[0])
    def _():
        shape = out_d_ref.shape[1:]
        out_d_ref[0] = jnp.full(shape, BIG, jnp.float32)
        out_i_ref[0] = jax.lax.broadcasted_iota(jnp.int32, shape, 0)


def _held(i, n):
    """Index-map row of grid step ``i`` for a page operand: ``i``, held at
    the last page holding work (``n[0] - 1``) past it, so that a step past
    it asks for the block the step before had and the pipeline fetches
    nothing."""
    return jnp.maximum(jnp.minimum(i, n[0] - 1), 0)


def _page_count(n_pages, nb):
    """The scalar-prefetch operand ``(1,) i32``: ``n_pages``, or every
    step of the grid when None."""
    if n_pages is None:
        return jnp.full((1,), nb, jnp.int32)
    return jnp.reshape(n_pages, (1,)).astype(jnp.int32)


def _scan_batched_topk_kernel(
    ids_ref, n_ref, q_ref, blk_ref, bias_ref, out_d_ref, out_i_ref, *,
    k: int, in_order: bool,
):
    # q_ref: (Q, d) resident; blk_ref: (1, BS, d); bias_ref: (1, BS, 1);
    # out: (1, k, Q) dists + slot indices, queries along the lanes.
    def topk():
        q = q_ref[...].astype(jnp.float32)        # (Q, d)
        b = blk_ref[0].astype(jnp.float32)        # (BS, d)
        d = _page_dists(q, b, in_order=in_order).T + bias_ref[0]  # (BS, Q)
        return _kmin(d, k=k, axis=0)              # (k, Q)

    _page_topk_or_empty(n_ref, out_d_ref, out_i_ref, topk)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def scan_batched_topk(
    unique_blocks: jax.Array,  # (NB,) i32 unique block pool indices (>=0)
    queries: jax.Array,        # (Q, d)
    blocks: jax.Array,         # (B, BS, d)
    slot_bias: jax.Array,      # (NB, BS) f32 — 0 live, +BIG dead
    *,
    k: int,
    n_pages: jax.Array | None = None,  # () i32 — pages holding work
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Batch-dedup paged scan with fused per-(page, query) k-min.

    Returns ``(dists (NB, k, Q), slots (NB, k, Q))`` — page-major with
    the queries along the lanes, the layout in which a page's Q·k
    candidates are stored densely.  Only the first ``n_pages`` pages
    (all of them when None) are fetched and scored; the rows past them
    hold an empty page's candidates."""
    nb = unique_blocks.shape[0]
    q_n, dim = queries.shape
    _, bs, _ = blocks.shape
    assert k <= bs, (k, bs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((q_n, dim), lambda i, ids, n: (0, 0)),
            pl.BlockSpec((1, bs, dim),
                         lambda i, ids, n: (ids[_held(i, n)], 0, 0)),
            pl.BlockSpec((1, bs, 1), lambda i, ids, n: (_held(i, n), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, k, q_n), lambda i, ids, n: (i, 0, 0)),
            pl.BlockSpec((1, k, q_n), lambda i, ids, n: (i, 0, 0)),
        ],
    )
    interpret = resolve_interpret(interpret)
    return pl.pallas_call(
        functools.partial(_scan_batched_topk_kernel, k=k, in_order=interpret),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nb, k, q_n), jnp.float32),
            jax.ShapeDtypeStruct((nb, k, q_n), jnp.int32),
        ],
        interpret=interpret,
    )(unique_blocks, _page_count(n_pages, nb), queries, blocks,
      slot_bias[:, :, None])


def _scan_batched_topk_q8_kernel(
    ids_ref, n_ref, q_ref, blk_ref, bias_ref, sz_ref, out_d_ref, out_i_ref,
    *, k: int, in_order: bool,
):
    # Batched dequant-fused variant: sz_ref (1, 1, 2) carries the unique
    # page's [scale, zero] (one posting owns each block, so the page has a
    # single parameter pair no matter how many queries probe it).
    def topk():
        q = q_ref[...].astype(jnp.float32)        # (Q, d)
        b = _dequant(blk_ref[0], sz_ref[0])       # (BS, d)
        d = _page_dists(q, b, in_order=in_order).T + bias_ref[0]  # (BS, Q)
        return _kmin(d, k=k, axis=0)              # (k, Q)

    _page_topk_or_empty(n_ref, out_d_ref, out_i_ref, topk)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def scan_batched_topk_q8(
    unique_blocks: jax.Array,  # (NB,) i32 unique block pool indices (>=0)
    queries: jax.Array,        # (Q, d)
    blocks: jax.Array,         # (B, BS, d) int8 codes
    slot_bias: jax.Array,      # (NB, BS) f32 — 0 live, +BIG dead
    page_sz: jax.Array,        # (NB, 2) f32 — per-page [scale, zero]
    *,
    k: int,
    n_pages: jax.Array | None = None,  # () i32 — pages holding work
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Batch-dedup paged scan over int8 codes with in-kernel dequant.

    Same contract as `scan_batched_topk`."""
    nb = unique_blocks.shape[0]
    q_n, dim = queries.shape
    _, bs, _ = blocks.shape
    assert k <= bs, (k, bs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((q_n, dim), lambda i, ids, n: (0, 0)),
            pl.BlockSpec((1, bs, dim),
                         lambda i, ids, n: (ids[_held(i, n)], 0, 0)),
            pl.BlockSpec((1, bs, 1), lambda i, ids, n: (_held(i, n), 0, 0)),
            pl.BlockSpec((1, 1, 2), lambda i, ids, n: (_held(i, n), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, k, q_n), lambda i, ids, n: (i, 0, 0)),
            pl.BlockSpec((1, k, q_n), lambda i, ids, n: (i, 0, 0)),
        ],
    )
    interpret = resolve_interpret(interpret)
    return pl.pallas_call(
        functools.partial(
            _scan_batched_topk_q8_kernel, k=k, in_order=interpret
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nb, k, q_n), jnp.float32),
            jax.ShapeDtypeStruct((nb, k, q_n), jnp.int32),
        ],
        interpret=interpret,
    )(unique_blocks, _page_count(n_pages, nb), queries, blocks,
      slot_bias[:, :, None], page_sz[:, None, :])
