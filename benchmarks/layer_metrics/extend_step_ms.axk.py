"""Median ``pio:seq.extend`` span of the traced stretch, in ms: one batch of
up to 4 extensions of 4 positions through the absorbed-MLA program over
latent caches of up to 49 blocks, dispatch to result: ``extend_step_ms.seq``'s
reading, in this stack's cell under a name of its own (``AXK_SPANS.md`` says
why)."""


def read(ctx):
    return ctx["bench"].lib("layer_metrics/extend_step_ms.seq").read(ctx)
