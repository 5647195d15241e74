"""What a session query costs the serving host path in front of the step
worker, in ms: the median over the traced stretch's requests of
``pio:http.request`` less the ``pio:serve.wait`` inside it. The bodies carry
the whole history (up to 32,840 items here, 260 KB: parse, admission, the
known-item accounting over every item, hand-over, serialise and write), under
whatever the worker thread, which launches a program every 20-30 ms, leaves
of the interpreter: ``front_self_ms.seq``'s reading on this cell's
requests."""


def read(ctx):
    return ctx["bench"].lib("layer_metrics/front_self_ms.seq").read(ctx)
