"""Median ``pio:seq.extend`` span of the traced stretch, in ms: one batch of
up to 8 extensions of 4 positions through the extension program (layers 0-17
over the new positions: nine states stepped in place, eight rings, the one
span written and walked; then each session's LAST row through layers 18-31:
seven more walks of that span, as far as the batch's longest session),
dispatch to result: ``extend_step_ms.seq``'s reading, in this stack's cell
under a name of its own (``PHI_SPANS.md`` says why)."""


def read(ctx):
    return ctx["bench"].lib("layer_metrics/extend_step_ms.seq").read(ctx)
