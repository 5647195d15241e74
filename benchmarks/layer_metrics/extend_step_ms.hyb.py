"""Median ``pio:seq.extend`` span of the traced stretch, in ms:
``extend_step_ms.seq``'s reading, in this stack's cell under a name of its own
(``HYB_SPANS.md`` says why). One batch of extensions (up to 16 sessions, up to
4 new positions each) through the hybrid stack's extension program — nine
recurrent states stepped in their slots, one layer's keys and values appended
— dispatch to result."""


def read(ctx):
    return ctx["bench"].lib("layer_metrics/extend_step_ms.seq").read(ctx)
