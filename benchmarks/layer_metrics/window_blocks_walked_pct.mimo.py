"""The blocks of 128 cached positions the window layers WALKED over the
blocks a walk from 0 to the same reach would have taken, in %, over the
traced run's measured window (``prefill_`` + ``extend_window_blocks`` over
``*_window_blocks_from0``: what the programs counted on the device, per layer
and real session, at the window's two ends). Lower is better, and it falls
with reach: a chunk at offset 0 walks 4 of 4, one at 24,064 at most 6 of 192.
None where the program counts no such blocks (the parent)."""


def read(ctx):
    counts = ctx["bench"].lib("seq_counts")
    walked = [counts.delta(ctx, f"{kind}_window_blocks", window=True)
              for kind in ("prefill", "extend")]
    from0 = [counts.delta(ctx, f"{kind}_window_blocks_from0", window=True)
             for kind in ("prefill", "extend")]
    if None in walked or None in from0 or sum(from0) <= 0:
        return None
    return 100.0 * sum(walked) / sum(from0)
