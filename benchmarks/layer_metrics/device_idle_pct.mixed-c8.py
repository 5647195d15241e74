"""Share of the traced stretch in which no operation ran on the device, in %
(``trace_reduce.idle_pct``). With 7 of the model's 48 layers here the host's
share of a query is larger than in the deployment."""


def read(ctx):
    return ctx["bench"].lib("trace_reduce").idle_pct(ctx.get("traced"))
