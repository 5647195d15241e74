"""Sessions an extension program ran for, on average, over the traced run's
measured window (``extend_rows`` over ``extend_runs``: the engine's own
counters at the window's two ends): how many of the 16 connections' queries
one read of the weights served."""


def read(ctx):
    counts = ctx["bench"].lib("seq_counts")
    rows = counts.delta(ctx, "extend_rows", window=True)
    runs = counts.delta(ctx, "extend_runs", window=True)
    if rows is None or not runs:
        return None
    return rows / runs
