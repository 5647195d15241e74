"""Share of the device's busy time over the traced stretch spent in the FFNs
every token passes (``seq.layer0.ffn_a``: the leading dense layer's SwiGLU of
18,432; ``seq.layer<i>.moe.shared``: the shared expert of each expert
layer), in %."""

PARTS = ("ffn_", "moe.shared")


def read(ctx):
    return ctx["bench"].lib("seq_counts").scope_share_pct(ctx, PARTS)
