"""The one traffic generator: an open-loop schedule from a mix's data file.

A traffic file (``bench/traffic/<name>.json``) gives

* ``rate_per_s``  — offered requests per second, all kinds together,
  averaged over the window;
* ``shares``      — ``{"search": s, "insert": i, "delete": d}`` by count;
* ``arrivals``    — the arrival process: ``{"process": "poisson"}``, or
  ``{"process": "on_off", "on_s": a, "off_s": b}``: bursts of ``a``
  seconds of Poisson arrivals at ``rate_per_s * (a + b) / a``, each
  followed by ``b`` seconds with none (default: poisson);
* ``queries``, ``inserts`` — the cluster weights that held-out queries and
  inserted vectors are drawn with: ``"model"`` (the configuration's own
  masses), ``"uniform"`` or ``"permuted"`` (the same masses moved to other
  clusters: the hot set moves) (default: model);
* ``threads``     — submitter threads (request ``j`` goes to ``j % threads``);
* ``check_sample`` — window searches whose answers are scored against the
  brute-force reference;
* ``source``, ``assumed`` — where the mix comes from and what it assumes
  (read by people, not by the generator).

Every request is one row: one query, one insert or one delete id.  To
keep the work of every seed the same, the SET of gaps and the count of
each kind do not depend on the seed (they come from a fixed generator);
the seed only orders them.  So two seeds offer the same number of
requests of each kind over the same span, in another order.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.datagen import rng_for

KINDS = ("search", "insert", "delete")
SEARCH, INSERT, DELETE = range(3)
_STREAM_ORDER = 11
_FIXED_SEED = 0x5EED


@dataclasses.dataclass
class Schedule:
    t: np.ndarray        # (n,) arrival offsets in seconds, ascending
    op: np.ndarray       # (n,) SEARCH / INSERT / DELETE
    threads: int

    def count(self, kind: int) -> int:
        return int((self.op == kind).sum())


def _on_time(arrivals: dict, seconds: float) -> tuple[float, callable]:
    """The seconds of the window in which requests arrive, and the map from
    an offset in that time to an offset in the window."""
    process = arrivals.get("process", "poisson")
    if process == "poisson":
        return seconds, lambda u: u
    if process != "on_off":
        raise ValueError(f"unknown arrival process {process!r}")
    on, off = float(arrivals["on_s"]), float(arrivals["off_s"])
    cycles = seconds / (on + off)
    return on * cycles, lambda u: u + np.floor(u / on) * off


def make_schedule(traffic: dict, seed: int, seconds: float,
                  rate_per_s: float | None = None) -> Schedule:
    rate = float(rate_per_s if rate_per_s is not None
                 else traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    span, to_window = _on_time(traffic.get("arrivals", {}), seconds)
    fixed = rng_for(_FIXED_SEED, int(round(rate * 1000)) + n)
    gaps = fixed.exponential(span / n, size=n)
    # the last arrival falls inside the window on every seed
    gaps *= span / (gaps.sum() + span / n)
    shares = np.asarray([float(traffic["shares"].get(k, 0)) for k in KINDS])
    counts = np.floor(shares / shares.sum() * n).astype(int)
    counts[int(np.argmax(shares))] += n - counts.sum()
    ops = np.repeat(np.arange(3), counts)
    rng = rng_for(seed, _STREAM_ORDER)
    t = to_window(np.cumsum(rng.permutation(gaps)))
    return Schedule(t=t, op=rng.permutation(ops).astype(np.int8),
                    threads=int(traffic["threads"]))
