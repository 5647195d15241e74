"""Median ``pio:batch.dispatch`` of the traced stretch with ``path=lone``, in
ms: one query alone through the engine's unbatched path (the Pallas
``topk_dot``), device included. Read per path because a stretch can hold
both, and their mixture says nothing about either."""


def read(ctx):
    spans = ctx["bench"].lib("program_spans")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    return spans.median_ms([s.end - s.start for s in spans.named(
        trace, "pio:batch.dispatch", path="lone")])
