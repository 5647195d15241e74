"""Share of the traced stretch in which no operation ran on the device, in %
(``trace_reduce.idle_pct``: 100 * (1 - busy / window), busy being the union of
device intervals). One name per cell kind, because each moves another
end-to-end metric."""


def read(ctx):
    return ctx["bench"].lib("trace_reduce").idle_pct(ctx.get("traced"))
