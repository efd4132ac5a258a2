"""WAL: percent of a traced slice of the window covered by `wal.append`
spans (encode, write, flush and any fsync the append makes) and
`wal.fsync` spans (with those outside an append: the forced group
commit)."""
from bench.metrics._spans import span_share


def read(r):
    return span_share(r, ("wal.append", "wal.fsync"))
