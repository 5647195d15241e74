"""Share of the positions the queries of the traced run's measured window
carried that the slot already held (states, rings AND span: a slot resumes
only from the end of its rows, ``LatentCache``'s whole-prefix rule), in %:
``cache_hit_tokens_pct.seq``'s reading, in this stack's cell under a name of
its own. A session of H items and 23 extensions carries about 24H positions
and finds about 23H of them cached."""


def read(ctx):
    return ctx["bench"].lib(
        "layer_metrics/cache_hit_tokens_pct.seq").read(ctx)
