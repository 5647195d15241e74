"""Share of the positions the queries of the traced run's measured window
carried that the latent cache already held, in % (``LatentCache.hit_tokens``
over hit + miss: the engine's own counters at the window's two ends). A
session of H items and five extensions carries about 6H positions and finds
about 5H of them cached."""


def read(ctx):
    counts = ctx["bench"].lib("seq_counts")
    hit = counts.delta(ctx, "hit_tokens", window=True)
    miss = counts.delta(ctx, "miss_tokens", window=True)
    if hit is None or miss is None or hit + miss <= 0:
        return None
    return 100.0 * hit / (hit + miss)
