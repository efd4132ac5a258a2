"""Seeded vector data for the benchmark's configurations.

A configuration's ``data`` block describes a cluster model: ``n_clusters``
centres, each with its own ``latent``-dimensional subspace along which its
members spread (real embeddings have a low intrinsic dimension; isotropic
clusters at d=100 have no near neighbours), cluster masses ``1/rank**skew``
(``skew`` 0 gives uniform masses), values rounded and clipped to the byte
range and stored as int8.  The arithmetic is a copy of the program's
``data/vectors.py`` ``make_spacev_bytes`` (SPACEV: centres 25·N(0,1),
spread 20, clip ±127), generalised so that the base set, the insert stream
and the held-out queries are separate draws from ONE model.

Every seed does the same work in another order: the model (centres,
subspaces, the moved masses of the insert stream), the base set and the
SET of inserted vectors are the same on every seed; the seed orders the
inserts and draws the held-out queries.  (Base sets drawn per seed left
some seeds with full postings that refuse inserts and others with none,
which made the tails of two seeds differ far more than two runs of one.)
Every array is a pure function of ``(seed, stream)``: the same seed gives
the same data, whatever order the draws are made in.
"""
from __future__ import annotations

import numpy as np

STREAM_MODEL, STREAM_BASE, STREAM_INSERT, STREAM_QUERY, STREAM_PERM = range(5)
MODEL_SEED = 0x5FE5


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any integer seed."""
    return np.random.default_rng([int(seed) % (1 << 63), stream])


class ClusterModel:
    """The cluster model of one configuration (the same on every seed)."""

    def __init__(self, data: dict):
        self.dim = int(data["dim"])
        self.k = int(data["n_clusters"])
        self.latent = int(data["latent"])
        self.spread = float(data["spread"])
        self.offset = float(data["offset"])
        self.lo, self.hi = int(data["lo"]), int(data["hi"])
        self.shift = int(data["shift"])
        rng = rng_for(MODEL_SEED, STREAM_MODEL)
        self.centers = (float(data["center_mean"]) + float(data["center_scale"])
                        * rng.normal(size=(self.k, self.dim))).astype(np.float32)
        self.bases = np.stack([
            np.linalg.qr(rng.normal(size=(self.dim, self.latent)))[0]
            for _ in range(self.k)
        ]).astype(np.float32)                         # (k, dim, latent)
        w = 1.0 / np.arange(1, self.k + 1) ** float(data["skew"])
        self.weights = w / w.sum()

    def permuted_weights(self) -> np.ndarray:
        """The same masses on other clusters: the hot set moves."""
        return self.weights[rng_for(MODEL_SEED, STREAM_PERM).permutation(self.k)]

    def weights_named(self, name: str) -> np.ndarray:
        """A traffic file's cluster weights: ``model``, ``uniform`` or
        ``permuted``."""
        if name == "model":
            return self.weights
        if name == "uniform":
            return np.full(self.k, 1.0 / self.k)
        if name == "permuted":
            return self.permuted_weights()
        raise ValueError(f"unknown cluster weights {name!r}")

    def draw(self, n: int, rng: np.random.Generator,
             weights: np.ndarray | None = None) -> np.ndarray:
        """``n`` vectors as int8 ``(n, dim)``."""
        p = self.weights if weights is None else weights
        assign = rng.choice(self.k, size=n, p=p)
        z = self.spread * rng.normal(size=(n, self.latent)).astype(np.float32)
        x = self.centers[assign] + self.offset
        order = np.argsort(assign, kind="stable")
        bounds = np.searchsorted(assign[order], np.arange(self.k + 1))
        for c in range(self.k):
            rows = order[bounds[c]:bounds[c + 1]]
            if rows.size:
                x[rows] += z[rows] @ self.bases[c].T
        x = np.clip(np.rint(x), self.lo, self.hi) - self.shift
        return x.astype(np.int8)


def make_cell_data(data: dict, seed: int, *, n_insert: int, n_query: int,
                   inserts: str = "model", queries: str = "model") -> dict:
    """The base set, the insert stream and the held-out queries of one run,
    all int8 ``(n, dim)``; ``inserts`` and ``queries`` name the cluster
    weights each is drawn with (``ClusterModel.weights_named``)."""
    model = ClusterModel(data)
    ins = model.draw(n_insert, rng_for(MODEL_SEED, STREAM_INSERT),
                     model.weights_named(inserts))
    return {
        "base": model.draw(int(data["n_live"]),
                           rng_for(MODEL_SEED, STREAM_BASE)),
        "insert": ins[rng_for(seed, STREAM_INSERT).permutation(n_insert)],
        "query": model.draw(n_query, rng_for(seed, STREAM_QUERY),
                            model.weights_named(queries)),
    }
