"""Median ``pio:seq.extend`` span of the traced stretch, in ms: one batch of
up to 8 extensions of 4 positions through the extension program (five window
layers walking at most three blocks of their rings whatever the reach, two
full layers walking their spans as far as the batch's longest session),
dispatch to result: ``extend_step_ms.seq``'s reading, in this stack's cell
under a name of its own (``MIMO_SPANS.md`` says why)."""


def read(ctx):
    return ctx["bench"].lib("layer_metrics/extend_step_ms.seq").read(ctx)
