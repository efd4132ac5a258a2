"""The plain reference and the recall it scores, on tiny exact cases."""
from __future__ import annotations

import numpy as np

from bench import reference
from bench.harness import scan_need


def test_brute_force_matches_exhaustive():
    rng = np.random.default_rng(0)
    vecs = rng.integers(-127, 128, size=(300, 12)).astype(np.int8)
    ids = np.arange(1000, 1300)
    q = rng.integers(-127, 128, size=(7, 12)).astype(np.int8)
    d, i = reference.brute_force_topk(vecs, ids, q, 5, chunk=64)
    full = ((q[:, None].astype(np.int64) - vecs[None]) ** 2).sum(-1)
    order = np.argsort(full, axis=1, kind="stable")[:, :5]
    assert np.array_equal(d, np.take_along_axis(full, order, 1))
    assert np.array_equal(np.sort(i, 1), np.sort(ids[order], 1))


def test_brute_force_pads_short_sets():
    vecs = np.array([[0, 0, 0], [5, 5, 5]], np.int8)
    d, i = reference.brute_force_topk(vecs, np.array([4, 5]),
                                      np.ones((1, 3), np.int8), 4)
    assert i.tolist() == [[4, 5, -1, -1]] and np.isinf(d[0, 2:]).all()


def test_recall_is_tie_aware():
    exact = np.array([0.0, 1.0, 1.0, 5.0])
    ids = np.array([3, 9, 9, 7])
    # kth distance 1.0: ids 3 and 9 count once each; 7 is too far
    assert reference.recall_row(ids, exact, 1.0, lambda i: True) == 2
    # an id that may not be returned never counts
    assert reference.recall_row(ids, exact, 1.0, lambda i: i != 3) == 1
    # a tie at the kth distance counts whichever id was returned
    assert reference.recall_row(np.array([8]), np.array([1.0]), 1.0,
                                lambda i: True) == 1


def test_int4_control_loses_precision():
    x = np.array([[-128, -1, 0, 15, 16, 127]], np.int8)
    assert reference.quantize(x, 8).tolist() == x.astype(float).tolist()
    assert reference.quantize(x, 4).tolist() == [[-128, -16, 0, 0, 16, 112]]


def test_scan_need_counts_distinct_pages():
    leaves = {"centroids": np.array([[0.0, 0], [10, 0], [0, 10], [50, 50]],
                                    np.float32),
              "valid": np.array([True, True, True, False]),
              "posting_len": np.array([33, 5, 64, 7]),
              "block_size": 32, "dim": 2, "itemsize": 1, "nprobe": 2}
    q = np.array([[1, 0], [9, 1]], np.int8)
    need = scan_need(leaves, q)
    # both queries probe postings 0 and 1: 2 + 1 pages, each streamed once
    assert need["bytes"] == 3 * 32 * 2 + q.astype(np.float32).nbytes
    assert need["flops"] == 2.0 * 2 * (33 + 5) * 2
