"""Share of the device's busy time over the traced stretch spent in the
ROUTED part of the expert layers (``seq.layer<i>.moe.route|experts``: the
sigmoid router with its selection bias, the sort, the streamed or grouped
kernel over the 16 held experts), in %. The shared expert (``moe.shared``)
and the leading dense layer are not in it: every token passes them."""

PARTS = ("moe.route", "moe.experts")


def read(ctx):
    return ctx["bench"].lib("seq_counts").scope_share_pct(ctx, PARTS)
