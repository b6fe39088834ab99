"""A test per-layer metric: the row sums the window made."""


def read(rec):
    return rec.get("sums")
