"""Share of the device's busy time over the traced stretch spent in the
ROUTED part of the expert layers (``seq.layer<i>.moe.route|experts``: the
sigmoid router with its choice of groups, the sort, the streamed or grouped
kernel), in %. The shared expert (``moe.shared``) is counted with the dense
FFNs (``dense_ffn_device_share_pct.axk``): every token passes it."""

PARTS = ("moe.route", "moe.experts")


def read(ctx):
    return ctx["bench"].lib("seq_counts").scope_share_pct(ctx, PARTS)
