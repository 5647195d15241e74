"""Share of the positions the queries of the traced run's measured window
carried that the cache already held (latent AND index key: they stand and
fall together), in %: ``cache_hit_tokens_pct.seq``'s reading, in this
stack's cell under a name of its own. A session of H items and nine
extensions carries about 10H positions and finds about 9H of them cached."""


def read(ctx):
    return ctx["bench"].lib(
        "layer_metrics/cache_hit_tokens_pct.seq").read(ctx)
