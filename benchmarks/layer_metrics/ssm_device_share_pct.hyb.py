"""Share of the device's busy time over the traced stretch spent in the
Mamba-2 mixers (``seq.layer<i>.mamba2_a.ssm.in_proj|conv|scan|out_proj``), in
%: the self time of the operations traced under those ``jax.named_scope``s,
through the program's ``obs/jaxmon.SCOPE_MAPS``
(``seq_counts.scope_share_pct``)."""

PARTS = ("mamba2_",)


def read(ctx):
    return ctx["bench"].lib("seq_counts").scope_share_pct(ctx, PARTS)
