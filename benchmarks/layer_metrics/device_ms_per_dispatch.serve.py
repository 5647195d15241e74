"""Device-busy ms per dispatch: the union of the intervals in which an
operation ran on the device during the traced stretch, over the dispatches
the MicroBatcher counted in that stretch."""


def read(ctx):
    traced, h0, h1 = ctx.get("traced"), ctx.get("hist0"), ctx.get("hist1")
    if not traced or not h0 or not h1:
        return None
    n = h1["dispatches"] - h0["dispatches"]
    if n <= 0 or traced["busy_s"] <= 0:
        return None
    return traced["busy_s"] / n * 1e3
