"""Share of the positions the queries of the traced run's measured window
carried that the cache already held, in %: ``cache_hit_tokens_pct.seq``'s
reading (``LatentCache.hit_tokens`` over hit + miss), in this engine's cell
under a name of its own (``GEN_SPANS.md`` says why). Under the block-causal
mask only whole shared blocks count: a follow-up finds its history's whole
blocks and writes over the slate the slot still held. A session of H items
and three follow-ups carries about 4H positions and finds about 3H of them
cached."""


def read(ctx):
    return ctx["bench"].lib(
        "layer_metrics/cache_hit_tokens_pct.seq").read(ctx)
