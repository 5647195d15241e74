"""Median ``pio:seq.block_step`` span of the traced stretch, in ms: one block
forward (up to 8 rows of 4 positions, denoise and commit rows together)
through the block program, dispatch to the rule's decision on the host
(``models/sessionrec.SeqStackModel._block_forward`` fetches it inside the
span). A slate is 12-15 of them."""


def read(ctx):
    spans = ctx["bench"].lib("program_spans")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    return spans.median_ms([
        s.end - s.start for s in spans.named(trace, "pio:seq.block_step")])
