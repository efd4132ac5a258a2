"""A cell's configuration and traffic cut to a size the CPU runs in seconds
(the XLA scan path, a few thousand vectors); the shapes of the real
configuration files are kept where the CPU allows."""
from __future__ import annotations

import copy

from bench import registry

TINY_INDEX = {
    "block_size": 8, "max_blocks_per_posting": 8, "num_blocks": 2048,
    "num_postings_cap": 256, "num_vectors_cap": 16384, "split_limit": 48,
    "merge_limit": 6, "reassign_range": 8, "reassign_budget": 128,
    "replica_count": 2, "nprobe": 32, "jobs_per_round": 4,
    "use_pallas_scan": False, "scan_schedule": "per_query",
    "scan_page_budget": 0,
}


def tiny_cell(workload: str, *, rate: float = 40.0):
    """``(config, traffic)`` of ``workload`` at the tiny size."""
    bench = registry.load()
    cell = registry.workload(bench, workload)
    config = copy.deepcopy(registry.config(bench, cell["config"]))
    traffic = copy.deepcopy(registry.traffic(cell["traffic"]))
    config["index"].update(TINY_INDEX)
    config["serve"].update({"nprobe": 32, "max_batch": 32})
    config["maintenance"]["jobs_per_round"] = 4
    config["data"].update({"n_live": 3000, "n_clusters": 20})
    traffic.update({"rate_per_s": rate, "check_sample": 64})
    return config, traffic


def fresh_batch_buffers(monkeypatch) -> None:
    """Make the serving queue form each micro-batch in a fresh buffer.

    On the CPU, JAX's asynchronous dispatch reads a numpy argument in
    place, and the queue refills one staging buffer per bucket for the
    next batch while the previous dispatch may not yet have read it: under
    load, the answers of one batch then come back for the queries of
    another (a fault of the program, first under Open questions in
    PERF.md).  It does not show with synchronous CPU dispatch or without
    the reuse.  The tiny runs test the harness, so they sidestep it."""
    from repro.serve import queue

    orig = queue.RequestQueue.__init__

    def init(self, *args, **kw):
        kw["reuse_staging"] = False
        orig(self, *args, **kw)
    monkeypatch.setattr(queue.RequestQueue, "__init__", init)
