"""Share of the device's busy time over the traced stretch spent in the
self-decoder's attention mixers (``seq.layer<i>.gqa_window_a``: eight layers
over their rings; ``seq.layer17.gqa_a``: the full layer, which writes and
walks the span), projections, walks and ``W_o`` alike, in %
(``seq_counts.scope_share_pct``)."""

PARTS = ("gqa_window_a", "gqa_a")


def read(ctx):
    return ctx["bench"].lib("seq_counts").scope_share_pct(ctx, PARTS)
