"""chip_smoke.py on the CPU: it refuses to run, and its phases are right.

The script's device run needs a TPU; here the test steers around the
platform check and drives the same phase function at the SMOKE geometry,
so the phase logic and its reference are guarded without chip time.
"""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402


def test_main_refuses_without_a_tpu(capsys):
    """Under JAX_PLATFORMS=cpu the script exits non-zero and prints no
    result line: it never falls back to the CPU."""
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_brute_force_reference_matches_dense(rng):
    """The chunked reference equals a dense all-pairs top-k at tiny N,
    chunk boundaries included."""
    vecs = rng.integers(-127, 128, size=(203, 12)).astype(np.float32)
    ids = np.arange(1000, 1203)
    queries = rng.integers(-127, 128, size=(9, 12)).astype(np.float32)
    got_d, got_i = chip_smoke.brute_force_topk(vecs, ids, queries, 10,
                                               chunk=17)
    dense = ((queries[:, None, :] - vecs[None]) ** 2).sum(-1)
    order = np.argsort(dense, axis=1, kind="stable")[:, :10]
    np.testing.assert_allclose(got_d, np.take_along_axis(dense, order, 1))
    # ids agree wherever the distance is not tied with a neighbour
    np.testing.assert_array_equal(
        np.take_along_axis(dense, got_i - 1000, 1),
        np.take_along_axis(dense, order, 1),
    )


def test_recall_is_tie_aware():
    live = {1: np.zeros(2), 2: np.ones(2), 3: np.ones(2), 4: 3 * np.ones(2)}
    queries = np.ones((1, 2))
    ref_d = np.array([[0.0, 0.0]])
    # either tied id is a hit; a duplicate, a stranger or -1 is not
    assert chip_smoke.recall_at_k(np.array([[3, 2]]), queries, ref_d, live) == 1
    assert chip_smoke.recall_at_k(np.array([[3, 3]]), queries, ref_d, live) == .5
    assert chip_smoke.recall_at_k(np.array([[9, -1]]), queries, ref_d, live) == 0
    assert chip_smoke.recall_at_k(np.array([[4, 1]]), queries, ref_d, live) == 0


def test_phases_at_smoke_geometry(tmp_path):
    """Build → search → 1% update epoch + drain → search → checkpoint,
    close, recover, on the CPU at the SMOKE geometry: every phase check
    passes and the recovered service answers identically."""
    from repro.configs.spfresh import service_spec

    spec = service_spec(smoke=True, durable_root=str(tmp_path / "root"))
    res = chip_smoke.run_phases(spec, n=1500, n_queries=64, batch=64,
                                log=lambda _msg: None)
    svc = res.pop("service")
    try:
        assert svc.recovered
        assert res["search_initial_recall"] >= chip_smoke.RECALL_FLOOR
        assert res["search_updated_recall"] >= chip_smoke.RECALL_FLOOR
        assert res["deleted_returned"] == 0
        assert res["recovered_identical"]
        text = chip_smoke.search_step_text(svc, 64)
        # interpret mode on the CPU: no Mosaic kernel in the program
        assert "tpu_custom_call" not in text
    finally:
        svc.close()
