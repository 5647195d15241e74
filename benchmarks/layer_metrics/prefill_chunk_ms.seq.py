"""Median ``pio:seq.prefill_chunk`` span of the traced stretch, in ms: one
chunk of 512 positions of one session through the chunked-prefill program,
dispatch to result."""


def read(ctx):
    spans = ctx["bench"].lib("program_spans")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    return spans.median_ms([
        s.end - s.start
        for s in spans.named(trace, "pio:seq.prefill_chunk")])
