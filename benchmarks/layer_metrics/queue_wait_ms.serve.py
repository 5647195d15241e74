"""Median time a request of the traced stretch waited in the MicroBatcher's
queue before its dispatch began, in ms (``MicroBatcher.recent_splits()``: host
clock, taken by the program around its own dispatch)."""

import statistics


def read(ctx):
    splits = ctx.get("splits")
    if not splits:
        return None
    return statistics.median(q for q, _ in splits) * 1e3
