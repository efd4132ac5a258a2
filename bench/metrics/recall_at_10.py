"""Tie-aware recall@10 of 1,024 held-out queries searched after the
window, against the brute-force reference over the live set."""


def read(r):
    return r["recall_at_10"]
