"""Share of the traced stretch's dispatches that carried one query alone,
in % (``MicroBatcher.histogram()``, difference over the stretch). A lone
query takes the engine's unbatched path; under concurrency every such
dispatch is one the batcher could have filled."""


def read(ctx):
    h0, h1 = ctx.get("hist0"), ctx.get("hist1")
    if not h0 or not h1:
        return None
    dispatches = h1["dispatches"] - h0["dispatches"]
    lone = (h1["batchSizeHistogram"].get("1", 0)
            - h0["batchSizeHistogram"].get("1", 0))
    return 100.0 * lone / dispatches if dispatches > 0 else None
