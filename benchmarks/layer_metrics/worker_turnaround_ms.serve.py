"""The batcher worker's turnaround, in ms: the median time from the end of
one ``pio:batch.dispatch`` to the start of the next (``pio:batch.deliver``,
the loop, ``pio:batch.collect``) while a request was waiting: some
``pio:serve.wait`` began before the first dispatch ended and lasted beyond the
second (a member of the first is woken long before that). With one connection
nobody waits and there is nothing to read."""


def read(ctx):
    spans = ctx["bench"].lib("program_spans")
    trace = spans.trace_of(ctx)
    if trace is None:
        return None
    dispatches = sorted(spans.named(trace, "pio:batch.dispatch"),
                        key=lambda s: s.start)
    waits = spans.named(trace, "pio:serve.wait")
    turnarounds = [
        b.start - a.end for a, b in zip(dispatches, dispatches[1:])
        if any(w.start < a.end and w.end >= b.end for w in waits)]
    return spans.median_ms(turnarounds)
