"""Padding waste of the shape buckets, an exact count: padded job rows over
all job rows the buckets carry."""


def read(run):
    c = run["counters"]
    rows = c.get("used_rows", 0) + c.get("padded_rows", 0)
    if not rows:
        return None
    return 100.0 * c["padded_rows"] / rows
