"""Share of the device's busy time over the traced stretch spent in the
grouped-query attention mixers (``seq.layer<i>.gqa_a``: projections, q/k
norms, RoPE, the cache write and the block loop over cached keys and values),
in %: the self time of the operations traced under those
``jax.named_scope``s, through the program's ``obs/jaxmon.SCOPE_MAPS``
(``seq_counts.scope_share_pct``)."""

PARTS = ("gqa_",)


def read(ctx):
    return ctx["bench"].lib("seq_counts").scope_share_pct(ctx, PARTS)
