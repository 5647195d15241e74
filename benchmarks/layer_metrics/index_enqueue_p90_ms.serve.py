"""The long end of what a search spends before its kernel is launched, in ms:
the 90th percentile (nearest rank) of the traced stretch's
``pio:index.enqueue`` spans, the spans whose median is
``index_enqueue_ms.serve``. The span is skewed: under 32 connections most
enqueues pay one hand-over of the interpreter to the handlers the last
dispatch woke, and some pay several, so a change that takes the long ones away
(PR 30 did) moves this and the idle seconds inside the span well before it
moves the median. A program that opens no such span gives nothing to read."""

import math


def read(ctx):
    spans = ctx["bench"].lib("program_spans")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    took = sorted(s.end - s.start for s in
                  spans.named(trace, "pio:index.enqueue"))
    if not took:
        return None
    return took[math.ceil(0.9 * len(took)) - 1] / 1e6
