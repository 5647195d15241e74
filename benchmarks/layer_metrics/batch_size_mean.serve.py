"""Queries per dispatch over the traced stretch: the difference of
``MicroBatcher.histogram()`` between the traced stretch's start and its end."""


def hist_delta(h0, h1):
    """(dispatches, queries) between two histogram() snapshots."""
    a = {int(k): v for k, v in h0["batchSizeHistogram"].items()}
    b = {int(k): v for k, v in h1["batchSizeHistogram"].items()}
    dispatches = sum(b.get(k, 0) - a.get(k, 0) for k in b)
    queries = sum(k * (b.get(k, 0) - a.get(k, 0)) for k in b)
    return dispatches, queries


def read(ctx):
    h0, h1 = ctx.get("hist0"), ctx.get("hist1")
    if not h0 or not h1:
        return None
    dispatches, queries = hist_delta(h0, h1)
    return queries / dispatches if dispatches else None
