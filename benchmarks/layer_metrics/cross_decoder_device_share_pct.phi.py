"""Share of the device's busy time over the traced stretch spent in the
cross-decoder's mixers (``seq.layer<i>.gmu_a``: seven gated memory units;
``seq.layer<i>.gqa_cross_a``: seven cross-attention layers, each a walk of
layer 17's span), in % (``seq_counts.scope_share_pct``): what the answered
rows cost beyond layers 0-17, their MLPs apart."""

PARTS = ("gmu_a", "gqa_cross_a")


def read(ctx):
    return ctx["bench"].lib("seq_counts").scope_share_pct(ctx, PARTS)
