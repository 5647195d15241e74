"""Median ``pio:seq.extend`` span of the traced stretch, in ms: one batch of
extensions through the absorbed-MLA program, dispatch to result
(``models/sessionrec.SeqStackModel.step`` blocks on it inside the span)."""


def read(ctx):
    spans = ctx["bench"].lib("program_spans")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    return spans.median_ms([s.end - s.start
                            for s in spans.named(trace, "pio:seq.extend")])
