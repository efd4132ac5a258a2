"""Maintenance: postings over the split limit at the window's close."""


def read(r):
    return float(r["backlog_end"])
