"""The search-path Pallas kernels compile for a TPU v5e at production widths.

Compiles — does not run — ``l2_topk_tiles`` and the four posting-scan
top-k kernels for a described ``v5e:2x2`` topology at the spfresh-1b
per-shard geometry (``configs/spfresh.py``: d=100, 32-vector pages,
262,144 pages, 65,536 centroids, nprobe=64 over 4-page postings, a
32,768-page budget, 1,024-query batches).  The TPU compiler enforces what
interpret mode cannot: block tiling, VMEM and SMEM limits, device memory.
Each test asserts that the compiled program calls the Mosaic kernel
(``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.spfresh import CONFIG_PAGED as CFG
from repro.configs.spfresh import SEARCH_Q
from repro.kernels.l2_topk.kernel import l2_topk_tiles
from repro.kernels.posting_scan import kernel as K

NB = CFG.nprobe * CFG.max_blocks_per_posting      # pages per query
K_PAGE = 10                                       # search k per page


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_l2_topk_tiles_compiles(one_chip, dtype):
    p = CFG.num_postings_cap
    text = _compiled_text(
        lambda q, c, s: l2_topk_tiles(
            q, c, s, k=CFG.nprobe, interpret=False
        ),
        _sds(one_chip, (SEARCH_Q, CFG.dim), dtype),
        _sds(one_chip, (p, CFG.dim), dtype),
        _sds(one_chip, (1, p), jnp.float32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("pool", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_scan_per_query_topk_compiles(one_chip, pool):
    text = _compiled_text(
        lambda t, q, b, bias: K.scan_per_query_topk(
            t, q, b, bias, k=K_PAGE, interpret=False
        ),
        _sds(one_chip, (SEARCH_Q, NB), jnp.int32),
        _sds(one_chip, (SEARCH_Q, CFG.dim), jnp.float32),
        _sds(one_chip, (CFG.num_blocks, CFG.block_size, CFG.dim), pool),
        _sds(one_chip, (SEARCH_Q, NB, CFG.block_size), jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_scan_per_query_topk_q8_compiles(one_chip):
    text = _compiled_text(
        lambda t, q, b, bias, sz: K.scan_per_query_topk_q8(
            t, q, b, bias, sz, k=K_PAGE, interpret=False
        ),
        _sds(one_chip, (SEARCH_Q, NB), jnp.int32),
        _sds(one_chip, (SEARCH_Q, CFG.dim), jnp.float32),
        _sds(one_chip, (CFG.num_blocks, CFG.block_size, CFG.dim), jnp.int8),
        _sds(one_chip, (SEARCH_Q, NB, CFG.block_size), jnp.float32),
        _sds(one_chip, (SEARCH_Q, NB, 2), jnp.float32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("pool", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_scan_batched_topk_compiles(one_chip, pool):
    budget = CFG.scan_page_budget
    text = _compiled_text(
        lambda u, q, b, bias: K.scan_batched_topk(
            u, q, b, bias, k=K_PAGE, interpret=False
        ),
        _sds(one_chip, (budget,), jnp.int32),
        _sds(one_chip, (SEARCH_Q, CFG.dim), jnp.float32),
        _sds(one_chip, (CFG.num_blocks, CFG.block_size, CFG.dim), pool),
        _sds(one_chip, (budget, CFG.block_size), jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_scan_batched_topk_q8_compiles(one_chip):
    budget = CFG.scan_page_budget
    text = _compiled_text(
        lambda u, q, b, bias, sz: K.scan_batched_topk_q8(
            u, q, b, bias, sz, k=K_PAGE, interpret=False
        ),
        _sds(one_chip, (budget,), jnp.int32),
        _sds(one_chip, (SEARCH_Q, CFG.dim), jnp.float32),
        _sds(one_chip, (CFG.num_blocks, CFG.block_size, CFG.dim), jnp.int8),
        _sds(one_chip, (budget, CFG.block_size), jnp.float32),
        _sds(one_chip, (budget, 2), jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_insert_step_routes_with_the_topk_op(one_chip):
    """The insert step's routing top-k over every centroid lowers to the
    chip's TopK op, not a sort of all of them a row, while the step also
    returns each row's routes (the backpressure's primaries)."""
    from repro.core import lire
    from repro.core.types import make_empty_state

    state = jax.tree.map(
        lambda x: _sds(one_chip, x.shape, x.dtype),
        jax.eval_shape(lambda: make_empty_state(CFG)),
    )
    b = 8
    text = _compiled_text(
        lire.insert_batch, state,
        _sds(one_chip, (b, CFG.dim), jnp.float32),
        _sds(one_chip, (b,), jnp.int32),
        _sds(one_chip, (b,), jnp.bool_),
    )
    assert 'custom_call_target="TopK"' in text
