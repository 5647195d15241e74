"""Share of the device's busy time over the traced stretch spent in the two
FULL layers' mixers (``seq.layer<i>.gqa_a``: projections, RoPE, the span's
write, the walk from block 0 to the reach, ``W_o``), in %: where all of a
step's growth with reach lies."""

PARTS = ("gqa_a",)


def read(ctx):
    return ctx["bench"].lib("seq_counts").scope_share_pct(ctx, PARTS)
