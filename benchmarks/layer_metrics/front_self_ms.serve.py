"""What a request costs the serving host path in front of the batcher, in ms:
the median over the traced stretch's requests of ``pio:http.request`` (request
read -> response written) less the ``pio:serve.wait`` inside it (blocked until
the batcher's answer). Parse, admission, hand-over, serialise and write, under
whatever the other handler threads leave of the interpreter."""


def read(ctx):
    spans = ctx["bench"].lib("program_spans")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    return spans.median_ms(spans.less_children(
        trace, "pio:http.request", "pio:serve.wait"))
