"""Share of the device's busy time over the traced stretch spent in the nine
Mamba-1 mixers (``seq.layer<i>.mamba1_a.ssm.in_proj|conv|scan|out_proj``), in
%: the self time of the operations traced under those ``jax.named_scope``s
(``seq_counts.scope_share_pct``)."""

PARTS = ("mamba1_",)


def read(ctx):
    return ctx["bench"].lib("seq_counts").scope_share_pct(ctx, PARTS)
