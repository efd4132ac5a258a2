"""PartitionSpec rules for every model family + the index.

Axis semantics on the production mesh (see launch/mesh.py):
  * ``pod``   — outermost replication/DP axis (multi-pod only)
  * ``data``  — DP/FSDP axis
  * ``model`` — TP/EP/vocab axis; also the index-shard axis

Rules of thumb applied here:
  * params: FSDP over ``data`` on the d_model-ish dimension, TP over
    ``model`` on heads/ffn/vocab/experts
  * batch: sharded over (pod, data)
  * optimizer state: identical specs as the param it tracks
  * a weight axis is sharded over ``model`` only when divisible by the
    model-axis size (checked by the caller via divisor arguments)
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import PartitionSpec as P


def data_axes(multi_pod: bool):
    return ("pod", "data") if multi_pod else ("data",)


def make_replicated_mesh(n_replicas: int, n_shards: int,
                         axes: tuple[str, str] = ("data", "model")):
    """The 2-axis (data, model) mesh of a replicated sharded service:
    the model axis shards postings (unchanged), the data axis holds
    ``n_replicas`` full copies of the index.  Needs
    ``n_replicas * n_shards`` devices."""
    assert n_replicas >= 1 and n_shards >= 1
    return jax.make_mesh((n_replicas, n_shards), axes)


def replica_submeshes(mesh, replica_axis: str = "data"):
    """Split a replicated mesh into one single-row submesh per replica
    (each over the remaining axes).  Row 0 is the primary's mesh; every
    replica's shard_map'd steps compile against its own row, so the
    per-shard step code is identical to the unreplicated path."""
    from jax.sharding import Mesh

    import numpy as np

    axis = mesh.axis_names.index(replica_axis)
    devs = np.moveaxis(np.asarray(mesh.devices), axis, 0)
    rest = tuple(a for a in mesh.axis_names if a != replica_axis)
    return [Mesh(devs[i], rest) for i in range(devs.shape[0])]


def current_mesh():
    """The ambient abstract mesh set by ``jax.set_mesh``, or None when no
    mesh is active."""
    am = jax.sharding.get_abstract_mesh()
    return am if am.axis_names else None


def _axes_size(am, entry) -> int:
    if entry is None:
        return 1
    names = entry if isinstance(entry, tuple) else (entry,)
    size = 1
    for n in names:
        size *= am.shape[n]
    return size


def _guarded_constraint(x, am, spec_entries):
    """Apply with_sharding_constraint, dropping axes that don't divide."""
    entries = []
    for dim, entry in zip(x.shape, spec_entries):
        if entry is not None and dim % _axes_size(am, entry) != 0:
            entry = None  # degrade: replicate this dim
        entries.append(entry)
    return jax.lax.with_sharding_constraint(x, P(*entries))


def act_constraint(x, *tail):
    """Mesh-adaptive activation sharding constraint.

    Shards dim 0 over every non-'model' mesh axis and the remaining dims per
    ``tail`` (e.g. ``act_constraint(x, None, 'model')`` for a (B, S, d)
    residual stream).  Dims that don't divide their axis set are left
    replicated.  No-op when tracing without a mesh context (CPU smoke
    tests) — the dry-run sets the mesh via ``jax.set_mesh``.
    """
    am = current_mesh()
    if am is None or "model" not in am.axis_names:
        return x
    da = tuple(a for a in am.axis_names if a != "model")
    return _guarded_constraint(x, am, (da if da else None, *tail))


def act_constraint_leading(x, lead, *tail):
    """Like :func:`act_constraint` but dim 0 shards over ``lead`` (e.g.
    'model' for expert-parallel buffers) and dim 1 over the data axes."""
    am = current_mesh()
    if am is None or "model" not in am.axis_names:
        return x
    da = tuple(a for a in am.axis_names if a != "model")
    return _guarded_constraint(x, am, (lead, da if da else None, *tail))


def act_constraint_flat2d(x):
    """Rows of a 2D buffer sharded over ('model', data-axes) flattened —
    the flat form of an (E over model, C over data) expert buffer, placed
    BEFORE the split-dim reshape so GSPMD treats the reshape as free."""
    am = current_mesh()
    if am is None or "model" not in am.axis_names:
        return x
    da = tuple(a for a in am.axis_names if a != "model")
    return _guarded_constraint(x, am, (("model", *da), None))


# ---------------------------------------------------------------------------
# LM family
# ---------------------------------------------------------------------------

def lm_param_specs(cfg, *, model_size: int = 16, multi_pod: bool = False):
    """Pytree of PartitionSpec matching transformer.init_params structure."""
    da = data_axes(multi_pod)
    fs = da[-1]  # FSDP axis ("data")
    kv_width = cfg.n_kv_heads * cfg.hd
    kv_model = "model" if kv_width % model_size == 0 else None
    layer = {
        "ln1": P(None, None),
        "ln2": P(None, None),
        "wq": P(None, fs, "model"),
        "wk": P(None, fs, kv_model),
        "wv": P(None, fs, kv_model),
        "wo": P(None, "model", fs),
    }
    if cfg.qkv_bias:
        layer["bq"] = P(None, "model")
        layer["bk"] = P(None, kv_model)
        layer["bv"] = P(None, kv_model)
    if cfg.moe:
        e_model = "model" if cfg.n_experts % model_size == 0 else None
        layer["moe"] = {
            "router": P(None, fs, None),
            "wi_gate": P(None, e_model, fs, None),
            "wi_up": P(None, e_model, fs, None),
            "wo": P(None, e_model, None, fs),
        }
    else:
        layer["mlp"] = {
            "wi_gate": P(None, fs, "model"),
            "wi_up": P(None, fs, "model"),
            "wo": P(None, "model", fs),
        }
    return {
        "embed": P("model", fs),
        "layers": layer,
        "final_norm": P(None),
        "lm_head": P(fs, "model"),
    }


def lm_batch_specs(kind: str, *, multi_pod: bool = False):
    da = data_axes(multi_pod)
    if kind in ("train", "prefill"):
        return {"tokens": P(da, None), "labels": P(da, None)} if kind == "train" \
            else {"tokens": P(da, None)}
    if kind == "decode":
        return {
            "cache": {
                "k": P(None, da, None, None, None),
                "v": P(None, da, None, None, None),
            },
            "tokens": P(da),
            "pos": P(),
        }
    raise ValueError(kind)


def lm_cache_specs(multi_pod: bool = False):
    # (L, B, S, KH, hd): batch over data axes, SEQUENCE over model —
    # kv-head counts (1..8) don't divide the 16-way model axis, and a 32k
    # cache replicated over model would blow per-device HBM.
    da = data_axes(multi_pod)
    return {
        "k": P(None, da, "model", None, None),
        "v": P(None, da, "model", None, None),
    }


# ---------------------------------------------------------------------------
# GNN family — edge-parallel: edges sharded over every axis, nodes replicated
# ---------------------------------------------------------------------------

def gnn_param_specs(params_tree: Any):
    return jax.tree_util.tree_map(lambda _: P(), params_tree)


def gnn_batch_specs(batch_tree: dict, *, multi_pod: bool = False):
    axes = (("pod", "data", "model") if multi_pod else ("data", "model"))
    specs = {}
    for k, v in batch_tree.items():
        if k in ("edge_src", "edge_dst"):
            specs[k] = P(axes)
        elif k == "n_graphs":
            specs[k] = None
        else:
            specs[k] = P(*([None] * getattr(v, "ndim", 0)))
    return specs


# ---------------------------------------------------------------------------
# Recsys family — tables row-sharded over model, batch over (pod, data)
# ---------------------------------------------------------------------------

def recsys_param_specs(params_tree: Any, *, model_size: int = 16,
                       multi_pod: bool = False):
    """Any leaf with >= 2**16 rows is treated as an embedding table
    (row-sharded over 'model'); everything else FSDP over 'data' on dim 0
    when divisible, else replicated."""
    da = data_axes(multi_pod)
    fs = da[-1]

    import math

    def rule(leaf):
        shape = leaf.shape
        if (len(shape) == 2 and shape[0] >= (1 << 16)
                and shape[0] % model_size == 0):
            return P("model", None)
        # FSDP only pays for itself on big weights: sharding a tiny tower
        # MLP over 'data' forces the huge per-candidate activations through
        # contraction-partial all-reduces (§Perf: 512 MB/step at
        # retrieval_cand).  Replicate anything under 2^22 elements.
        if (len(shape) >= 1 and shape[0] % model_size == 0
                and shape[0] >= 256 and math.prod(shape) >= (1 << 22)):
            return P(fs, *([None] * (len(shape) - 1)))
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map(rule, params_tree)


def recsys_batch_specs(batch_tree: dict, *, multi_pod: bool = False):
    da = data_axes(multi_pod)
    da_size = 32 if multi_pod else 16
    specs = {}
    for k, v in batch_tree.items():
        ndim = getattr(v, "ndim", 0)
        if k == "candidate_ids":
            # candidates shard over 'model' (1M % 16 == 0; the full data×
            # model product does not divide 1M)
            specs[k] = P("model")
        elif ndim == 0:
            specs[k] = P()
        elif v.shape[0] % da_size != 0:
            # retrieval_cand has batch=1: replicate tiny leading dims
            specs[k] = P(*([None] * ndim))
        else:
            specs[k] = P(da, *([None] * (ndim - 1)))
    return specs


# ---------------------------------------------------------------------------
# Optimizer state: mirror the param specs
# ---------------------------------------------------------------------------

def opt_state_specs(param_specs_tree: Any):
    return {
        "m": param_specs_tree,
        "v": param_specs_tree,
        "count": P(),
    }
