"""Schedules and data: the same for one seed, different across seeds, and
the same amount of work on every seed."""
from __future__ import annotations

import json
import shutil

import numpy as np
import pytest

from bench import datagen, registry
from bench.traffic import DELETE, INSERT, SEARCH, make_schedule

BIG_SEED = 2**31 + 12345
MIXES = ["spacev-churn", "sift-churn"]


@pytest.mark.parametrize("mix", MIXES)
def test_schedule_repeats_per_seed(mix):
    tr = registry.traffic(mix)
    a = make_schedule(tr, BIG_SEED, 3.0)
    b = make_schedule(tr, BIG_SEED, 3.0)
    c = make_schedule(tr, 7, 3.0)
    assert np.array_equal(a.t, b.t) and np.array_equal(a.op, b.op)
    assert not np.array_equal(a.t, c.t)
    # the same work on every seed: counts per kind and the set of gaps
    for kind in (SEARCH, INSERT, DELETE):
        assert a.count(kind) == c.count(kind)
    gaps = lambda s: np.sort(np.diff(np.concatenate([[0.0], s.t])))
    assert np.allclose(gaps(a), gaps(c))
    assert a.t[-1] < 3.0 and np.all(np.diff(a.t) >= 0)
    n = len(a.t)
    assert n == round(tr["rate_per_s"] * 3.0)


def test_shares_by_count():
    tr = registry.traffic("spacev-churn")
    s = make_schedule(tr, 3, 10.0)
    n = len(s.t)
    sh = tr["shares"]
    tot = sum(sh.values())
    for kind, name in ((SEARCH, "search"), (INSERT, "insert"),
                       (DELETE, "delete")):
        assert abs(s.count(kind) - n * sh[name] / tot) <= 1


DATA = {"n_live": 400, "dim": 16, "n_clusters": 12, "skew": 1.2,
        "latent": 4, "center_mean": 0.0, "center_scale": 25.0,
        "spread": 20.0, "offset": 0.0, "lo": -127, "hi": 127, "shift": 0}


@pytest.mark.parametrize("inserts", ["model", "permuted"])
def test_data_repeats_per_seed(inserts):
    a = datagen.make_cell_data(DATA, BIG_SEED, n_insert=50, n_query=30,
                               inserts=inserts)
    b = datagen.make_cell_data(DATA, BIG_SEED, n_insert=50, n_query=30,
                               inserts=inserts)
    c = datagen.make_cell_data(DATA, 5, n_insert=50, n_query=30,
                               inserts=inserts)
    for k in ("base", "insert", "query"):
        assert a[k].dtype == np.int8 and np.array_equal(a[k], b[k])
    # every seed: the same base set and the same inserts, in another order
    assert np.array_equal(a["base"], c["base"])
    assert not np.array_equal(a["insert"], c["insert"])
    key = lambda x: sorted(map(bytes, x))
    assert key(a["insert"]) == key(c["insert"])
    assert not np.array_equal(a["query"], c["query"])
    assert a["base"].shape == (400, 16)


def test_byte_shift_keeps_distances():
    """SIFT's uint8 bytes shifted into int8 keep every L2 distance."""
    sift = dict(DATA, lo=0, hi=255, shift=128, center_mean=40.0)
    a = datagen.make_cell_data(sift, 9, n_insert=1, n_query=1)["base"]
    x = datagen.make_cell_data(dict(sift, shift=0), 9, n_insert=1,
                               n_query=1)["base"]
    x = x.astype(np.int64) & 0xFF            # the uint8 bytes
    assert np.array_equal(a.astype(np.int64) + 128, x)
    assert a.min() == -128                   # byte 0 is kept, not clipped
    d_shift = ((a[:5, None].astype(np.int64) - a[None, :5]) ** 2).sum(-1)
    d_raw = ((x[:5, None] - x[None, :5]) ** 2).sum(-1)
    assert np.array_equal(d_shift, d_raw)


def test_permuted_inserts_move_the_hot_set():
    m = datagen.ClusterModel(DATA)
    w = m.permuted_weights()
    assert np.allclose(np.sort(w), np.sort(m.weights))
    assert not np.allclose(w, m.weights)


def _new_mix(tmp_path, name: str, **changes):
    """A traffic mix added as a new file alone, read back by the registry."""
    root = tmp_path / "repo"
    shutil.copytree(registry.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    mix = dict(registry.traffic("spacev-churn"), **changes)
    (root / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    return registry.traffic(name, root)


def test_bursty_mix_from_a_new_file(tmp_path):
    tr = _new_mix(tmp_path, "bursty", rate_per_s=40.0,
                  arrivals={"process": "on_off", "on_s": 1.0, "off_s": 3.0})
    s = make_schedule(tr, BIG_SEED, 20.0)
    assert len(s.t) == 800 and s.t[-1] < 20.0
    phase = np.mod(s.t, 4.0)
    assert np.all(phase < 1.0)               # nothing arrives while off
    per_burst = np.bincount((s.t // 4.0).astype(int), minlength=5)
    assert per_burst.min() > 100             # about 160 a burst: 4x the mean
    other = make_schedule(tr, 7, 20.0)
    assert other.count(INSERT) == s.count(INSERT)
    assert np.all(np.mod(other.t, 4.0) < 1.0)


def _share_of_hottest(queries: np.ndarray) -> float:
    m = datagen.ClusterModel(DATA)
    d = ((queries[:, None].astype(np.float32) - m.centers[None]) ** 2).sum(-1)
    return float((d.argmin(1) == int(np.argmax(m.weights))).mean())


def test_uniform_query_mix_from_a_new_file(tmp_path):
    tr = _new_mix(tmp_path, "uniform-q", queries="uniform")
    kw = dict(n_insert=10, n_query=1200, inserts=tr["inserts"])
    uni = datagen.make_cell_data(DATA, BIG_SEED, queries=tr["queries"], **kw)
    zipf = datagen.make_cell_data(DATA, BIG_SEED, queries="model", **kw)
    assert np.array_equal(uni["base"], zipf["base"])
    # 12 clusters: the hottest holds about 0.39 of Zipf(1.2) draws, 1/12 of
    # uniform ones
    assert _share_of_hottest(zipf["query"]) > 0.3
    assert _share_of_hottest(uni["query"]) < 0.15


def test_unknown_process_or_weights_is_refused():
    tr = dict(registry.traffic("spacev-churn"), arrivals={"process": "x"})
    with pytest.raises(ValueError):
        make_schedule(tr, 1, 2.0)
    with pytest.raises(ValueError):
        datagen.make_cell_data(DATA, 1, n_insert=1, n_query=1, queries="x")
