"""Share of the device's busy time over the traced stretch spent in
the latent-attention mixers (``seq.layer<i>.mla_a|mla_b``), in %: the self time of the operations traced under those
``jax.named_scope``s, through the program's ``obs/jaxmon.SCOPE_MAPS``
(``seq_counts.scope_share_pct``)."""

PARTS = ("mla_",)


def read(ctx):
    return ctx["bench"].lib("seq_counts").scope_share_pct(ctx, PARTS)
