"""Device-busy ms per training step: the union of the intervals in which an
operation ran on the device during the traced epochs, over their steps."""


def read(ctx):
    traced = ctx.get("traced")
    if not traced or not traced.get("steps") or traced["busy_s"] <= 0:
        return None
    return traced["busy_s"] / traced["steps"] * 1e3
