"""Share of the positions the queries of the traced run's measured window
carried that their slot already held, in %: ``cache_hit_tokens_pct.seq``'s
reading (``LatentCache.hit_tokens`` over hit + miss), in this stack's cell
under a name of its own (``HYB_SPANS.md`` says why). Under the whole-prefix
rule of a stack with recurrent state a hit is a session that grew: a session
of H items and seven extensions carries about 8H positions and finds about 7H
of them held."""


def read(ctx):
    return ctx["bench"].lib(
        "layer_metrics/cache_hit_tokens_pct.seq").read(ctx)
