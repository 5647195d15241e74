"""Rows the chunk programs carried through layers 18-31 over the tokens they
prefilled, in %, over the traced run's measured window (``prefill_cross_rows``
over ``prefill_tokens``: what the programs counted on the device at the
window's two ends). One row a history whose last positions are a chunk's: a
history of 4,096 reads 1 of 4,096 (0.024 %); a program that ran the
cross-decoder over every position would read 100. None where the program
counts no such rows (the parent)."""


def read(ctx):
    counts = ctx["bench"].lib("seq_counts")
    rows = counts.delta(ctx, "prefill_cross_rows", window=True)
    tokens = counts.delta(ctx, "prefill_tokens", window=True)
    if rows is None or not tokens:
        return None
    return 100.0 * rows / tokens
