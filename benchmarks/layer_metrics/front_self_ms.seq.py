"""What a session query costs the serving host path in front of the step
worker, in ms: the median over the traced stretch's requests of
``pio:http.request`` (request read -> response written) less the
``pio:serve.wait`` inside it (blocked until the step worker's answer). The
bodies carry the whole history (up to 5,700 items, 45 KB): parse, admission,
the known-item accounting over every item, hand-over, serialise and write,
under whatever the worker thread and the other handlers leave of the
interpreter. ``front_self_ms.serve``'s spans, on this cell's requests."""


def read(ctx):
    spans = ctx["bench"].lib("program_spans")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    return spans.median_ms(spans.less_children(
        trace, "pio:http.request", "pio:serve.wait"))
