"""Median time an EXTENSION of the traced stretch waited between its submit
and its admission to a step, in ms (``StepWorker.recent_splits()``, host
clock, taken by the program): here the step in flight is as often a chunk of
somebody's first query (18 ms) as an extension batch (28 ms), so this wait is
most of what the median query adds to its own batch:
``extend_wait_ms.seq``'s reading on this cell's queries."""


def read(ctx):
    return ctx["bench"].lib("layer_metrics/extend_wait_ms.seq").read(ctx)
