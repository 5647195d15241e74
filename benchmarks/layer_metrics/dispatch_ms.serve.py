"""Median dispatch time of the traced stretch's requests, device included,
in ms: from the start of the batch's dispatch to the answer on the host
(``MicroBatcher.recent_splits()``, one entry per request)."""

import statistics


def read(ctx):
    splits = ctx.get("splits")
    if not splits:
        return None
    return statistics.median(d for _, d in splits) * 1e3
