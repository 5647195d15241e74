"""Median ``pio:batch.dispatch`` of the traced stretch with ``path=batched``,
in ms: several queries as one batch through the model's one retriever (since
PR 28 ``topk_dot`` at the batch's row bucket, over the index's one device copy
of the table), device included. A cell with one connection has none."""


def read(ctx):
    spans = ctx["bench"].lib("program_spans")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    return spans.median_ms([s.end - s.start for s in spans.named(
        trace, "pio:batch.dispatch", path="batched")])
