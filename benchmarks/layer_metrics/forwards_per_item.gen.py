"""Block-forward rows (denoise + commit, a history's known blocks among the
commit rows) per item the rule unmasked, over the traced run's measured
window: the engine's own counters at the window's two ends. Two positions a
denoise forward and one commit a block of four is 0.75; a follow-up's known
blocks and a block the history's left-over items opened add to it. A model
that accepts more positions a forward, or a commit folded into the next
block's first denoise forward, lowers it."""


def read(ctx):
    counts = ctx["bench"].lib("seq_counts")
    rows = [counts.delta(ctx, k, window=True)
            for k in ("denoise_rows", "commit_rows")]
    items = counts.delta(ctx, "positions_unmasked", window=True)
    if None in rows or not items:
        return None
    return sum(rows) / items
