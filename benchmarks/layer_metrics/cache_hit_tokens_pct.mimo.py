"""Share of the positions the queries of the traced run's measured window
carried that the slot already held (span AND rings: they stand and fall
together, ``LatentCache``'s ring rule), in %: ``cache_hit_tokens_pct.seq``'s
reading, in this stack's cell under a name of its own. A session of H items
and seven extensions carries about 8H positions and finds about 7H of them
cached."""


def read(ctx):
    return ctx["bench"].lib(
        "layer_metrics/cache_hit_tokens_pct.seq").read(ctx)
