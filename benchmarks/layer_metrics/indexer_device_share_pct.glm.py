"""Share of the device's busy time over the traced stretch spent in the
learned index (``seq.layer<i>.mla_a.index``: its three projections and the
scores against the cached index keys; ``seq.layer<i>.mla_a.select``: each
row's exact top 2,048, by ``topk_mask``'s bisection in a chunk and, since
PR 44, in an extension too: no ``jax.lax.top_k``), in %. None where the program has no such scope (the parent)."""

PARTS = ("mla_a.index", "mla_a.select")


def read(ctx):
    return ctx["bench"].lib("seq_counts").scope_share_pct(ctx, PARTS)
