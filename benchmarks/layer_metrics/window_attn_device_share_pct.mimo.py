"""Share of the device's busy time over the traced stretch spent in the five
WINDOW layers' mixers (``seq.layer<i>.gqa_window_a`` and the walk's scope
inside it, ``.attend``: projections, RoPE, the ring's write, the walk over at
most six blocks of 128 cached positions, ``W_o``), in %. None where the
program has no such scope (the parent)."""

PARTS = ("gqa_window_a",)


def read(ctx):
    return ctx["bench"].lib("seq_counts").scope_share_pct(ctx, PARTS)
