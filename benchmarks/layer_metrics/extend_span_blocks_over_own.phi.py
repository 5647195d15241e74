"""Blocks of 512 cached positions of the ONE span that its eight readers
(layer 17 and the seven cross layers) walked for the extended rows over what
each row's own reach would have taken, over the traced run's measured window
(``extend_span_blocks_walked`` over ``extend_span_blocks_own``: what the
extension program counted on the device at the window's two ends): every row
of a batch walks as far as its LONGEST, so a 600-event session batched with a
32,768-event one reads 65 blocks for its own 2. 1 is no waste. None where the
program does not count them (the parent)."""


def read(ctx):
    counts = ctx["bench"].lib("seq_counts")
    walked = counts.delta(ctx, "extend_span_blocks_walked", window=True)
    own = counts.delta(ctx, "extend_span_blocks_own", window=True)
    if walked is None or not own:
        return None
    return walked / own
