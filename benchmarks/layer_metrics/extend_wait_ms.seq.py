"""Median time an EXTENSION of the traced stretch waited between its submit
and its admission to a step, in ms: the wait for the step in flight
(``StepWorker.recent_splits()``: host clock, taken by the program; the third
element says whether the model called the query an extension)."""

import statistics


def read(ctx):
    waits = [s[0] for s in ctx.get("splits") or () if len(s) > 2 and s[2]]
    return statistics.median(waits) * 1e3 if waits else None
