"""Share of the traced stretch in which no operation ran on the device, in %
(``trace_reduce.idle_pct``). The model is whole here (all 32 layers), so the
host's share of a query is the deployment's own."""


def read(ctx):
    return ctx["bench"].lib("trace_reduce").idle_pct(ctx.get("traced"))
