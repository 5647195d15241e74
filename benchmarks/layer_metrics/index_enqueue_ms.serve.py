"""What a search spends before its kernel is launched, in ms: the median
``pio:index.enqueue`` span of the traced stretch (``index/exact.py
ExactIndex.search`` and the XLA scorer it falls back to: the shape discipline
over the query vectors and the exclusion lists, and the compiled call
returning, which carries the transfer of inputs that are host data). A lone
query pays it as plain interpreter time; under 32 connections every call into
the runtime inside the span is a place where the worker gives the interpreter
to the handlers the last dispatch woke and queues for it again, so the same
span reads several times longer there. A program that opens no such span
gives nothing to read."""


def read(ctx):
    spans = ctx["bench"].lib("program_spans")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    return spans.median_ms([s.end - s.start for s in
                            spans.named(trace, "pio:index.enqueue")])
