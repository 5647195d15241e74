"""Share of the device's busy time over the traced stretch spent in the six
expert layers (``seq.layer<i>.moe.route|experts``: the sigmoid router with
its selection bias, the sort, the streamed or grouped kernel over the 16 held
experts; this model has no shared expert), in %. The leading dense layer's
FFN is not in it: every token passes it."""

PARTS = ("moe.route", "moe.experts")


def read(ctx):
    return ctx["bench"].lib("seq_counts").scope_share_pct(ctx, PARTS)
