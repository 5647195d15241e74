"""Median ``pio:seq.extend`` span of the traced stretch, in ms: one batch of
up to 4 extensions of 4 positions through the extension program, each row
scoring its slot's index keys (up to 65 blocks a layer) and attending its
2,048 selected latents where they lie in the slot (a masked walk since PR 44,
no gather), dispatch to result: ``extend_step_ms.seq``'s reading,
in this stack's cell under a name of its own (``GLM_SPANS.md`` says why)."""


def read(ctx):
    return ctx["bench"].lib("layer_metrics/extend_step_ms.seq").read(ctx)
