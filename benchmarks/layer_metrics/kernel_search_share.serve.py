"""Share of the traced stretch's searches that the Pallas kernel answered, in
% (``pio:index.route`` markers with ``route=kernel`` over all of them: every
search of the retrieval index writes one inside its ``pio:index.search``, with
the route it took, ``kernel`` / ``xla_device`` / ``host``, and its ``rows``).
On a TPU every search inside the kernel's caps is the kernel's, a lone query's
and a micro-batch's alike, so both ALS serve cells should read 100; a search
the XLA scorer answered is a second pass over a second copy of the table. A
program that writes no such marker (before PR 28) gives nothing to read."""


def read(ctx):
    spans = ctx["bench"].lib("program_spans")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    routes = spans.named(trace, "pio:index.route")
    if not routes:
        return None
    kernel = sum(1 for s in routes if s.attrs.get("route") == "kernel")
    return 100.0 * kernel / len(routes)
