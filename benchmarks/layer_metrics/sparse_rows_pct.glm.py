"""Share of the real query rows of the traced run's measured window whose
reach exceeded ``index_topk``: the rows the index selects FOR, in %
(``prefill_index_sparse_rows + extend_index_sparse_rows`` over the two
programs' ``*_tokens``: the engine's own counters at the window's two ends).
Histories of 2,048 to 32,768 put four rows in five past 2,048. None where the
program counts no such rows (the parent)."""


def read(ctx):
    counts = ctx["bench"].lib("seq_counts")
    sparse = [counts.delta(ctx, f"{kind}_index_sparse_rows", window=True)
              for kind in ("prefill", "extend")]
    tokens = [counts.delta(ctx, f"{kind}_tokens", window=True)
              for kind in ("prefill", "extend")]
    if None in sparse or None in tokens or sum(tokens) <= 0:
        return None
    return 100.0 * sum(sparse) / sum(tokens)
