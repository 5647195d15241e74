"""The host's share of a dispatch, in ms: the median over the traced
stretch's ``pio:batch.dispatch`` spans of the span less the device-busy time
of the operations it started (``program_spans.host_ns_per_span``). What the
engine and the index spend around the device: look-up, padding, transfer, the
call, the copy back, decoding."""


def read(ctx):
    spans = ctx["bench"].lib("program_spans")
    trace = spans.trace_of(ctx)
    if trace is None or not trace.ops:
        return None
    return spans.median_ms(spans.host_ns_per_span(
        trace, spans.named(trace, "pio:batch.dispatch")))
